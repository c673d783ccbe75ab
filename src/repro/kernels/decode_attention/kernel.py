"""Flash-decoding Pallas TPU kernel: one query token vs. a long KV cache.

Grid: (batch, kv_heads, kv_blocks) with the kv dimension sequential, so
partial (max, denom, acc) accumulate in VMEM scratch — the TPU-native
analogue of GPU split-K flash decoding (TPU grids are sequential per
core; the LSE combine collapses into scratch accumulation). The query
block holds all G = H/KV query heads of one KV head so the (G, bk) score
matmul feeds the MXU. Per-sequence ``lengths`` mask the cache tail.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, scale: float, bk: int, n_kv_blocks: int,
                   window: Optional[int]):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)          # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)          # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)          # (bk, D)
    length = len_ref[0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if window is None:
        valid = kpos < length
    else:
        # ring cache: all W slots valid once the cache has wrapped
        valid = kpos < jnp.minimum(length, jnp.int32(window))
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == n_kv_blocks - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-37)).astype(o_ref.dtype)


def decode_attention_pallas(q, k_cache, v_cache, lengths, *,
                            window: Optional[int] = None, bk: int = 512,
                            interpret: bool = False):
    """q: (B, KV, G, D); caches: (B, KV, W, D); lengths: (B,).
    Returns (B, KV, G, D)."""
    B, KV, G, D = q.shape
    W = k_cache.shape[2]
    bk = min(bk, W)
    pad = (-W) % bk
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nk = (W + pad) // bk
    grid = (B, KV, nk)

    kernel = functools.partial(
        _decode_kernel, scale=1.0 / math.sqrt(D), bk=bk, n_kv_blocks=nk,
        window=window)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda b, h, j: (b,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, k_cache, v_cache)
    return out
