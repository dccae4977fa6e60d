"""RG-LRU linear recurrence kernel (Griffin) for TPU (Pallas).

Computes h_t = a_t * h_{t-1} + b_t over the sequence, given precomputed
gate products a, b (fp32): the memory-bound inner loop of the Griffin
block. Grid = (batch, d_blocks, s_blocks) with the sequence dimension
innermost ("arbitrary" semantics): the recurrent state h lives in VMEM
scratch and persists across sequence grid steps. Within a block a
``fori_loop`` steps through time on (blk_d,)-wide vectors.

This is the TPU-native adaptation of a GPU scan kernel: instead of a
warp-level prefix scan, the sequential dependence is carried block-to-block
in VMEM while the (batch × d) dimensions provide the parallelism that fills
the VPU lanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rg_lru_kernel(a_ref, b_ref, h0_ref, o_ref, h_scr, *, blk_s: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_scr[...] = h0_ref[0]  # (1, blk_d)

    def step(t, h):
        row = pl.ds(t, 1)
        h = a_ref[0, row, :] * h + b_ref[0, row, :]
        o_ref[0, row, :] = h
        return h

    h_scr[...] = jax.lax.fori_loop(0, blk_s, step, h_scr[...])


def rg_lru(
    a: jax.Array,  # (batch, seq, d) fp32 decay
    b: jax.Array,  # (batch, seq, d) fp32 gated input
    h0: jax.Array | None = None,  # (batch, d) initial state
    *,
    blk_s: int = 256,
    blk_d: int = 512,
    interpret: bool = False,
) -> jax.Array:
    bt, s, d = a.shape
    if h0 is None:
        h0 = jnp.zeros((bt, d), jnp.float32)
    # A unit axis makes the state block's last two dims (1, blk_d) match
    # the array's (1, d) — the TPU (8, 128) block rule.
    h0 = h0.reshape(bt, 1, d)
    blk_s = min(blk_s, s)
    blk_d = min(blk_d, d)
    grid = (bt, pl.cdiv(d, blk_d), pl.cdiv(s, blk_s))

    kernel = functools.partial(_rg_lru_kernel, blk_s=blk_s)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk_s, blk_d), lambda bi, di, si: (bi, si, di)),
            pl.BlockSpec((1, blk_s, blk_d), lambda bi, di, si: (bi, si, di)),
            pl.BlockSpec((1, 1, blk_d), lambda bi, di, si: (bi, 0, di)),
        ],
        out_specs=pl.BlockSpec((1, blk_s, blk_d), lambda bi, di, si: (bi, si, di)),
        out_shape=jax.ShapeDtypeStruct((bt, s, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, blk_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, b, h0)
