"""Fused LSTM cell kernel for TPU (Pallas) — the paper case-study hotspot.

One kernel fuses both gate matmuls (x@Wx + h@Wh), bias add and all four
gate nonlinearities + state update, instead of four XLA ops with HBM
round-trips between them. Weights are laid out (D, 4, H) so a hidden-block
grid tile can read all four gate slices contiguously.

Grid = (batch_blocks, hidden_blocks); the contraction dims (d_in, d_hidden)
are kept whole per tile (they fit VMEM for the case-study sizes; ops.py
asserts this). Gate math in fp32 on the VPU, matmuls on the MXU.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lstm_kernel(x_ref, h_ref, c_ref, wx_ref, wh_ref, b_ref, ho_ref, co_ref):
    x = x_ref[...]  # (blk_b, d_in)
    h = h_ref[...]  # (blk_b, H)
    c = c_ref[...].astype(jnp.float32)  # (blk_b, blk_h)
    wx = wx_ref[...]  # (d_in, 4, blk_h)
    wh = wh_ref[...]  # (H, 4, blk_h)
    b = b_ref[...]  # (4, blk_h)

    blk_b = x.shape[0]
    blk_h = c.shape[1]
    zx = jax.lax.dot_general(
        x, wx.reshape(wx.shape[0], -1), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    zh = jax.lax.dot_general(
        h, wh.reshape(wh.shape[0], -1), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    z = (zx + zh).reshape(blk_b, 4, blk_h) + b.astype(jnp.float32)[None]
    i, f, g, o = z[:, 0], z[:, 1], z[:, 2], z[:, 3]
    c_new = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    h_new = jax.nn.sigmoid(o) * jnp.tanh(c_new)
    co_ref[...] = c_new.astype(co_ref.dtype)
    ho_ref[...] = h_new.astype(ho_ref.dtype)


def lstm_cell(
    x: jax.Array,  # (B, d_in)
    h: jax.Array,  # (B, H)
    c: jax.Array,  # (B, H)
    wx: jax.Array,  # (d_in, 4, H)
    wh: jax.Array,  # (H, 4, H)
    b: jax.Array,  # (4, H)
    *,
    blk_b: int = 128,
    blk_h: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    bt, d_in = x.shape
    hidden = h.shape[1]
    blk_b = min(blk_b, bt)
    blk_h = min(blk_h, hidden)
    grid = (pl.cdiv(bt, blk_b), pl.cdiv(hidden, blk_h))

    out_shape = [
        jax.ShapeDtypeStruct((bt, hidden), x.dtype),
        jax.ShapeDtypeStruct((bt, hidden), x.dtype),
    ]
    ho, co = pl.pallas_call(
        _lstm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((blk_b, d_in), lambda bi, hi: (bi, 0)),
            pl.BlockSpec((blk_b, hidden), lambda bi, hi: (bi, 0)),
            pl.BlockSpec((blk_b, blk_h), lambda bi, hi: (bi, hi)),
            pl.BlockSpec((d_in, 4, blk_h), lambda bi, hi: (0, 0, hi)),
            pl.BlockSpec((hidden, 4, blk_h), lambda bi, hi: (0, 0, hi)),
            pl.BlockSpec((4, blk_h), lambda bi, hi: (0, hi)),
        ],
        out_specs=[
            pl.BlockSpec((blk_b, blk_h), lambda bi, hi: (bi, hi)),
            pl.BlockSpec((blk_b, blk_h), lambda bi, hi: (bi, hi)),
        ],
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(x, h, c, wx, wh, b)
    return ho, co
