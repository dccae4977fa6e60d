"""Tiled flash attention for TPU (Pallas): causal / sliding-window / GQA.

Layout: q (B*NQ, S, D), k/v (B*KVH, S, D). Grid = (bh, q_blocks, kv_blocks)
with the kv dimension innermost ("arbitrary" semantics): online-softmax
running stats (m, l, acc) live in VMEM scratch and persist across kv grid
steps; the output block is written on the last kv step.

MXU alignment: block sizes default to (128, 128); head_dim is padded to a
multiple of 128 by ops.py when needed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, window: int, blk_q: int, blk_k: int,
    n_kv_blocks: int, kv_len: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]  # (blk_q, d)
    k = k_ref[0]  # (blk_k, d)
    v = v_ref[0]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (blk_q, blk_k)

    q_pos = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
    k_pos = ki * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
    mask = k_pos < kv_len  # real (non-padded) keys only
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_prev * corr + p.sum(axis=-1)
    m_scr[...] = m_new
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_scr[...] = acc_scr[...] * corr[:, None] + pv

    @pl.when(ki == n_kv_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # (BH, S, D) with BH = B * n_q_heads
    k: jax.Array,  # (BKV, S, D) with BKV = B * n_kv_heads
    v: jax.Array,
    *,
    n_q_heads: int,
    n_kv_heads: int,
    causal: bool = True,
    window: int = 0,
    blk_q: int = 128,
    blk_k: int = 128,
    kv_len: int = 0,  # number of real keys (0 -> all)
    interpret: bool = False,
) -> jax.Array:
    bh, sq, d = q.shape
    skv = k.shape[1]
    kv_len = kv_len or skv
    groups = n_q_heads // n_kv_heads
    n_q_blocks = pl.cdiv(sq, blk_q)
    n_kv_blocks = pl.cdiv(skv, blk_k)
    scale = 1.0 / np.sqrt(d)

    def q_index(bhi, qi, ki):
        return (bhi, qi, 0)

    def kv_index(bhi, qi, ki):
        b = bhi // n_q_heads
        h = bhi % n_q_heads
        return (b * n_kv_heads + h // groups, ki, 0)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        blk_q=blk_q, blk_k=blk_k, n_kv_blocks=n_kv_blocks, kv_len=kv_len,
    )
    return pl.pallas_call(
        kernel,
        grid=(bh, n_q_blocks, n_kv_blocks),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), q_index),
            pl.BlockSpec((1, blk_k, d), kv_index),
            pl.BlockSpec((1, blk_k, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, blk_q, d), q_index),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q,), jnp.float32),
            pltpu.VMEM((blk_q,), jnp.float32),
            pltpu.VMEM((blk_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
