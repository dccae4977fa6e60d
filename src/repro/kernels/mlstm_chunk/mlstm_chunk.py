"""Chunkwise mLSTM kernel for TPU (Pallas) — the kernel-level follow-through
of the xlstm_1_3b hillclimb (EXPERIMENTS.md §Perf).

The jnp chunked form already cut HBM traffic 139× by materializing the
(dh×dh) matrix memory per *chunk* instead of per *timestep*; this kernel
removes the remaining per-chunk HBM round-trip entirely: the state
(C, n, m) lives in VMEM scratch across the sequence-chunk grid dimension
("arbitrary" semantics — TPU grids iterate the minor dimension
sequentially), so HBM traffic is exactly the q/k/v/gate streams plus the
h output. Intra-chunk work is two MXU matmuls per chunk
((L,dh)·(dh,dh) inter + (L,L)·(L,dh) intra) plus VPU gate algebra.

Math is identical to repro.models.xlstm._mlstm_chunked (stabilized
exponential gating, see that docstring); validated against the sequential
per-step oracle in tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..pallas_compat import lane_prefix_sum

NEG_INF = -1e30


def _mlstm_chunk_kernel(
    q_ref, k_ref, v_ref, i_ref, f_ref, o_ref,
    c_scr, n_scr, m_scr,
    *, chunk: int,
):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        c_scr[...] = jnp.zeros_like(c_scr)
        n_scr[...] = jnp.zeros_like(n_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)

    L = chunk
    qb = q_ref[0].astype(jnp.float32)  # (L, dh)
    kb = k_ref[0].astype(jnp.float32)
    vb = v_ref[0].astype(jnp.float32)
    ib = i_ref[0, 0].astype(jnp.float32)  # (1, L) gate pre-activations
    fb = f_ref[0, 0].astype(jnp.float32)

    C_in = c_scr[...]  # (dh_v, dh_k)
    n_in = n_scr[...]  # (1, dh_k)
    m_in = m_scr[...]  # (1, 1)

    lf = jax.nn.log_sigmoid(fb)  # (1, L)
    b_cum = lane_prefix_sum(lf)
    x = ib - b_cum  # (1, L)
    # running max over j<=t via masked (L, L) max (L is small: O(L^2) VPU)
    tt = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    tri = jj <= tt
    rmax = jnp.max(jnp.where(tri, x, NEG_INF), axis=1)[None, :]  # (1, L)

    m_t = jnp.maximum(b_cum + m_in, rmax + b_cum)  # (1, L)
    inter = jnp.exp(b_cum + m_in - m_t)  # (1, L)
    # intra decay D_{tj} = exp(b_t - m_t + i_j - b_j), j <= t
    D = jnp.exp((b_cum - m_t)[0][:, None] + x[0][None, :])
    D = jnp.where(tri, D, 0.0)

    scores = jax.lax.dot_general(
        qb, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (L, L)
    W = D * scores
    num = inter[0][:, None] * jax.lax.dot_general(
        qb, C_in, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) + jax.lax.dot_general(W, vb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    den = inter * (qb @ n_in[0])[None, :] + W.sum(axis=1)[None, :]  # (1, L)
    h = num / jnp.maximum(jnp.abs(den[0]), 1.0)[:, None]
    o_ref[0] = h.astype(o_ref.dtype)

    # state update at t = L-1
    b_last = b_cum[:, L - 1 :]  # (1, 1)
    m_out = jnp.maximum(b_last + m_in, jnp.max(x, axis=1, keepdims=True) + b_last)
    s_out = jnp.exp(b_last + m_in - m_out)
    w_j = jnp.exp((b_last - b_cum) + ib - m_out)  # (1, L)
    kw = kb * w_j[0][:, None]  # (L, dh)
    c_scr[...] = s_out * C_in + jax.lax.dot_general(
        vb, kw, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (dh_v, dh_k)
    n_scr[...] = s_out * n_in + jnp.sum(kw, axis=0)[None, :]
    m_scr[...] = m_out


def mlstm_chunk(
    q: jax.Array,  # (BH, S, dh)
    k: jax.Array,
    v: jax.Array,
    i_gate: jax.Array,  # (BH, S) pre-activation input gate
    f_gate: jax.Array,  # (BH, S) pre-activation forget gate
    *,
    chunk: int = 64,
    interpret: bool = False,
) -> jax.Array:
    bh, s, dh = q.shape
    assert s % chunk == 0, (s, chunk)
    n_chunks = s // chunk

    qkv_spec = pl.BlockSpec((1, chunk, dh), lambda b, c: (b, c, 0))
    # Gates ride as (bh, n_chunks, 1, chunk): a chunk's block then ends in
    # (1, chunk), equal to the array's last two dims (TPU (8, 128) rule).
    gate_spec = pl.BlockSpec((1, 1, 1, chunk), lambda b, c: (b, c, 0, 0))
    kernel = functools.partial(_mlstm_chunk_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(bh, n_chunks),
        in_specs=[qkv_spec, qkv_spec, qkv_spec, gate_spec, gate_spec],
        out_specs=qkv_spec,
        out_shape=jax.ShapeDtypeStruct((bh, s, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((dh, dh), jnp.float32),
            pltpu.VMEM((1, dh), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v, i_gate.reshape(bh, n_chunks, 1, chunk),
      f_gate.reshape(bh, n_chunks, 1, chunk))
