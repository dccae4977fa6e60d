"""On-device text cleaning kernel (Pallas) — P3SAPP's cleaning stage on TPU.

This is the beyond-paper adaptation of the paper's core idea: instead of
merely overlapping host preprocessing with accelerator compute, the
character-level cleaning stages (ConvertToLower + RemoveHTMLTags +
RemoveUnwantedCharacters' character classes) run *on* the accelerator that
would otherwise idle.

Input: a (rows, width) uint8 matrix of padded text rows. One VMEM pass:

* lowercase via arithmetic range test (no gather — TPU-friendly),
* tag-span removal via a per-row cumulative depth (rows are independent,
  so a prefix sum along the width axis is exactly the span mask; Mosaic
  has no cumsum, so it is a log-step sum of lane rotations),
* unwanted-character classes mapped to space.

Output: cleaned bytes with removed positions already set to space; the
host only collapses whitespace (the only step needing compaction).
Grid over row blocks; width stays whole per block (row-local prefix sum).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..pallas_compat import lane_prefix_sum

SPACE = 32

# Rows are uint8, whose native TPU tile is (32, 128): row blocks stay a
# multiple of 32 and widths a multiple of 128.
ROW_TILE = 32
LANE_TILE = 128
# Elements per block: about six int32 temporaries of one block live at
# once in the prefix sum, so 2**18 elements keep a block near 6 MiB, inside
# the default scoped VMEM with the double-buffered uint8 in/out blocks.
_BLOCK_ELEMS = 1 << 18
_MAX_BLK_ROWS = 512


def block_rows(width: int) -> int:
    """Row-block height for a ``width``-byte row: the largest multiple of
    ``ROW_TILE`` (capped at 512) whose int32 temporaries fit one block's
    VMEM budget. Wide rows (abstracts run to several KB) get short blocks."""
    rows = (_BLOCK_ELEMS // max(width, 1)) // ROW_TILE * ROW_TILE
    return min(max(rows, ROW_TILE), _MAX_BLK_ROWS)


def _grid_call(kernel, rows: jax.Array, blk_rows: int | None, interpret: bool):
    n, width = rows.shape
    blk = min(blk_rows or block_rows(width), n)
    spec = pl.BlockSpec((blk, width), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n, blk),),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n, width), jnp.uint8),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(rows)


def _clean_kernel(x_ref, o_ref, *, strip_html: bool):
    x = x_ref[...].astype(jnp.int32)  # (blk_r, width)

    # ConvertToLower: A-Z -> a-z
    upper = (x >= 65) & (x <= 90)
    x = jnp.where(upper, x + 32, x)

    keep = jnp.ones_like(x, dtype=jnp.bool_)
    if strip_html:
        lt = (x == 60).astype(jnp.int32)  # '<'
        gt = (x == 62).astype(jnp.int32)  # '>'
        depth = lane_prefix_sum(lt - gt)
        keep = (depth == 0) & (x != 62)

    # RemoveUnwantedCharacters: anything outside [a-z] -> space
    is_word = (x >= 97) & (x <= 122)
    out = jnp.where(is_word & keep, x, SPACE)
    o_ref[...] = out.astype(jnp.uint8)


def text_clean(
    rows: jax.Array,  # (n_rows, width) uint8, space padded
    *,
    strip_html: bool = True,
    blk_rows: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    kernel = functools.partial(_clean_kernel, strip_html=strip_html)
    return _grid_call(kernel, rows, blk_rows, interpret)


def _scan_kernel(x_ref, o_ref, *, lower: bool, strip_html: bool, strip_parens: bool):
    """Megapass scan-pass kernel: the byte-exact device form of the fused
    backend's LUT/SPAN sweep (``bytesops._run_scan``).

    Unlike ``_clean_kernel`` this does NOT space-mask non-letters — later
    chain stages (contraction REPLACE) need the original punctuation — and
    removed span bytes become sentinel ``\\x00`` rather than space, so the
    host can delete them and land on exactly the loops-backend bytes.
    Survival uses ``depth <= 0`` (not ``== 0``): a stray ``>`` drives the
    depth negative and ``span_strip`` keeps the bytes that follow it.
    The paren span masks its opens/closes/deltas with the HTML span's
    aliveness, which makes the two parallel depth scans sequential-exact."""
    x = x_ref[...].astype(jnp.int32)  # (blk_r, width)
    if lower:
        upper = (x >= 65) & (x <= 90)
        x = jnp.where(upper, x + 32, x)
    alive = jnp.ones_like(x, dtype=jnp.bool_)
    if strip_html:
        lt = (x == 60).astype(jnp.int32)  # '<'
        gt = (x == 62).astype(jnp.int32)  # '>'
        depth = lane_prefix_sum(lt - gt)
        alive = (depth <= 0) & (x != 62)
    if strip_parens:
        opens = (x == 40) & alive  # '('
        closes = (x == 41) & alive  # ')'
        depth2 = lane_prefix_sum(opens.astype(jnp.int32) - closes.astype(jnp.int32))
        alive &= (depth2 <= 0) & ~closes
    o_ref[...] = jnp.where(alive, x, 0).astype(jnp.uint8)


def text_scan(
    rows: jax.Array,  # (n_rows, width) uint8, space padded
    *,
    lower: bool = True,
    strip_html: bool = False,
    strip_parens: bool = False,
    blk_rows: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    kernel = functools.partial(
        _scan_kernel, lower=lower, strip_html=strip_html, strip_parens=strip_parens
    )
    return _grid_call(kernel, rows, blk_rows, interpret)
