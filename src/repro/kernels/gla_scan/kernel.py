"""Chunked gated-linear-attention scan Pallas TPU kernel.

Serves RWKV6 (per-channel data-dependent decay + bonus ``u``) and
Mamba2/SSD (scalar-per-head decay). Grid: (batch, heads, chunks) with the
chunk dimension sequential; the recurrent state (K, V) is carried in VMEM
scratch across chunks. Per chunk:

  inter  = (q * exp(L_read)) @ S                         (MXU matmul)
  intra  = [q_t . k_j * exp(L_read_t - L_j)]_{j<=t} @ v   (pairwise-stable)
  S_new  = diag(exp(L_c)) S + (k * exp(L_c - L))^T v      (MXU matmul)

The pairwise log-difference form keeps strong decay (|log w| >> 1) from
overflowing — the same trick as the XLA path in
``repro.models.linear_attention``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _gla_kernel(q_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, s_out_ref, s_scr,
                *, mode: str, chunk: int, n_chunks: int, has_u: bool):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    qb = q_ref[0, 0].astype(jnp.float32)   # (C, K)
    kb = k_ref[0, 0].astype(jnp.float32)   # (C, K)
    vb = v_ref[0, 0].astype(jnp.float32)   # (C, V)
    lw = lw_ref[0, 0].astype(jnp.float32)  # (C, K)

    L = jnp.cumsum(lw, axis=0)             # inclusive cumulative log decay
    Lc = L[-1:, :]                         # (1, K) total chunk decay
    if mode == "rwkv":
        L_read = L - lw                    # exclusive: state before token t
    else:
        L_read = L                         # inclusive: state after update

    state = s_scr[...]                     # (K, V)
    q_sc = qb * jnp.exp(L_read)
    o_inter = jax.lax.dot_general(q_sc, state, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # intra-chunk pairwise form: (C, C, K) log-difference tensor
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    mask = (t_idx > j_idx) if mode == "rwkv" else (t_idx >= j_idx)
    diff = L_read[:, None, :] - L[None, :, :]          # (C, C, K)
    w_pair = jnp.where(mask[:, :, None], jnp.exp(diff), 0.0)
    att = jnp.einsum("tk,jk,tjk->tj", qb, kb, w_pair)
    o_intra = jax.lax.dot_general(att, vb, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    if has_u:
        u = u_ref[0].astype(jnp.float32)               # (K,)
        bonus = jnp.sum(qb * u[None, :] * kb, axis=1, keepdims=True)
        o_intra = o_intra + bonus * vb

    o_ref[0, 0] = (o_inter + o_intra).astype(o_ref.dtype)

    # state update
    k_dec = kb * jnp.exp(Lc - L)                       # (C, K)
    s_upd = jax.lax.dot_general(k_dec, vb, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    s_scr[...] = jnp.exp(Lc).T * state + s_upd

    @pl.when(c == n_chunks - 1)
    def _emit_state():
        s_out_ref[0, 0] = s_scr[...]


def gla_scan_pallas(q, k, v, log_w, u: Optional[jnp.ndarray] = None,
                    mode: str = "ssd", chunk: int = 128,
                    interpret: bool = False):
    """q/k/log_w: (B, H, T, K); v: (B, H, T, V); u: (H, K) or None.
    Returns (o (B, H, T, V), final_state (B, H, K, V))."""
    B, H, T, K = q.shape
    V = v.shape[-1]
    chunk = min(chunk, T)
    pad = (-T) % chunk
    if pad:
        pz = ((0, 0), (0, 0), (0, pad), (0, 0))
        q, k, v = jnp.pad(q, pz), jnp.pad(k, pz), jnp.pad(v, pz)
        log_w = jnp.pad(log_w, pz)  # log w = 0 => no decay for padding
    n = (T + pad) // chunk
    grid = (B, H, n)
    has_u = u is not None
    if u is None:
        u = jnp.zeros((H, K), q.dtype)

    kernel = functools.partial(_gla_kernel, mode=mode, chunk=chunk,
                               n_chunks=n, has_u=has_u)

    o, s_final = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, V), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, K), lambda b, h, c: (h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, V), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, K, V), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T + pad, V), v.dtype),
            jax.ShapeDtypeStruct((B, H, K, V), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, log_w, u)
    return o[:, :, :T], s_final
