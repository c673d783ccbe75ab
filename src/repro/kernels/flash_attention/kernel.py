"""Flash-attention Pallas TPU kernel (prefill/training path).

Online-softmax attention with GQA, causal and sliding-window masking.
Grid: (batch, q_heads, q_blocks, kv_blocks); the kv dimension is the
innermost sequential ("arbitrary") dimension so the running max/denom/
accumulator live in VMEM scratch across kv steps. Block shapes are
128-aligned for the MXU; K/V blocks for a query head are fetched from its
GQA-mapped KV head via the BlockSpec index map.
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: Optional[int],
                  seq_len: int, bq: int, bk: int, n_kv_blocks: int):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    i = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)            # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)            # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)            # (bk, D)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = (qpos < seq_len) & (kpos < seq_len)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                            # (bq, 1)
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


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           window: Optional[int] = None,
                           bq: int = 128, bk: int = 128,
                           interpret: bool = False):
    """q: (B, H, S, D); k/v: (B, KV, S, D). Returns (B, H, S, D).

    S is padded to block multiples internally; D should be MXU-friendly
    (the caller pads head_dim when needed).
    """
    B, H, S, D = q.shape
    KV = k.shape[1]
    assert H % KV == 0
    group = H // KV
    bq = min(bq, max(8, 1 << (S - 1).bit_length())) if S < bq else bq
    bk = min(bk, max(8, 1 << (S - 1).bit_length())) if S < bk else bk
    pad = (-S) % bq
    pad_k = (-S) % bk
    Sq, Sk = S + pad, S + pad_k
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq, nk = Sq // bq, Sk // bk
    grid = (B, H, nq, nk)

    kernel = functools.partial(
        _flash_kernel, scale=1.0 / math.sqrt(D), causal=causal,
        window=window, seq_len=S, bq=bq, bk=bk, n_kv_blocks=nk)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j, group=group: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j, group=group: (b, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :S]
