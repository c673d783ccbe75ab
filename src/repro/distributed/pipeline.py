"""Pipeline parallelism: GPipe-style microbatch schedule via shard_map +
collective_permute over a ``stage`` mesh axis.

The production meshes are 2D/3D without a dedicated stage axis; PP is an
*optional* layout for deployments that want it (the launcher builds a
(stage, data) mesh). The schedule below is the standard loop formulation:
at step t, stage s processes microbatch (t - s); activations hop one
stage per step via ppermute; the bubble is (S-1) steps of (M+S-1).

Gradient flow works through the same schedule because the whole thing is
differentiable jnp code (ppermute has a transpose rule).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, PartitionSpec as P


def pipeline_forward(stage_fn: Callable, n_stages: int, n_micro: int,
                     mesh: Mesh, stage_axis: str = "stage"):
    """Build fn(stage_params, x_micro) -> y_micro running under shard_map.

    stage_fn(params_for_stage, x) -> y is the per-stage computation.
    stage_params leaves have leading dim = n_stages (sharded over the
    stage axis); x_micro is (n_micro, mb, ...) replicated.
    """

    def per_stage(params, x_micro):
        # params: this stage's slice (leading dim 1); x_micro replicated
        params = jax.tree.map(lambda a: a[0], params)
        sid = jax.lax.axis_index(stage_axis)
        S, M = n_stages, n_micro
        T = M + S - 1
        mb_shape = x_micro.shape[1:]

        def step(carry, t):
            buf, outputs = carry
            # stage s works on microbatch (t - s) if 0 <= t - s < M
            mb_idx = t - sid
            active = (mb_idx >= 0) & (mb_idx < M)
            # stage 0 reads fresh input; others use the handed-off buffer
            x_in = jnp.where(
                sid == 0,
                x_micro[jnp.clip(mb_idx, 0, M - 1)],
                buf)
            y = stage_fn(params, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # last stage records output
            outputs = jax.lax.cond(
                active & (sid == S - 1),
                lambda o: o.at[jnp.clip(mb_idx, 0, M - 1)].set(y),
                lambda o: o,
                outputs)
            # hand activations to the next stage
            buf_next = jax.lax.ppermute(
                y, stage_axis, [(i, (i + 1) % S) for i in range(S)])
            return (buf_next, outputs), None

        buf0 = jnp.zeros(mb_shape, x_micro.dtype)
        out0 = jnp.zeros((M,) + mb_shape, x_micro.dtype)
        (_, outputs), _ = jax.lax.scan(step, (buf0, out0), jnp.arange(T))
        # only the last stage holds nonzero outputs; psum broadcasts them
        return jax.lax.psum(outputs, stage_axis)

    return jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(stage_axis), P()),
        out_specs=P(),
        check_vma=False)


def make_pp_mesh(n_stages: int, n_data: int = 1):
    return jax.make_mesh((n_stages, n_data), ("stage", "data"),
                         axis_types=(AxisType.Auto,) * 2)
