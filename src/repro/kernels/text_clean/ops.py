"""Jit'd public wrappers for the text-clean kernels + host bridging.

``clean_rows`` is the practical list[str] entry point: padding/packing on
the host, the character pipeline on device (compiled on TPU, interpret
elsewhere).

``scan_flat`` is the *backend* entry point used by
``repro.core.bytesops`` when ``REPRO_BYTES_BACKEND=pallas``: a flat
``\\x00``-separated uint8 buffer goes in, the megapass scan pass (lower +
span strips) runs on device over a padded (rows, width) matrix, and the
sentinel-marked removals are compacted back into a flat buffer —
byte-identical to the host scan.  It returns ``None`` whenever it declines
(no TPU and interpret not forced, padding blow-up, malformed buffer);
callers fall back to the host implementation, so declining is always
safe."""

from __future__ import annotations

import os
from functools import partial

import jax
import numpy as np

from ..pallas_compat import has_tpu
from .text_clean import LANE_TILE, ROW_TILE, block_rows, text_clean, text_scan


@partial(jax.jit, static_argnames=("strip_html", "blk_rows", "interpret"))
def text_clean_op(rows, *, strip_html: bool = True, blk_rows: int | None = None,
                  interpret: bool = False):
    return text_clean(rows, strip_html=strip_html, blk_rows=blk_rows, interpret=interpret)


@partial(
    jax.jit,
    static_argnames=("lower", "strip_html", "strip_parens", "blk_rows", "interpret"),
)
def text_scan_op(rows, *, lower: bool = True, strip_html: bool = False,
                 strip_parens: bool = False, blk_rows: int | None = None,
                 interpret: bool = False):
    return text_scan(rows, lower=lower, strip_html=strip_html,
                     strip_parens=strip_parens, blk_rows=blk_rows,
                     interpret=interpret)


def pack_rows(rows: list[str], width: int | None = None) -> np.ndarray:
    """Pad/truncate UTF-8 rows into a (n, width) uint8 matrix (space pad)."""
    enc = [r.encode("utf-8", errors="ignore") for r in rows]
    width = width or max((len(e) for e in enc), default=1)
    out = np.full((len(rows), width), 32, dtype=np.uint8)
    for i, e in enumerate(enc):
        out[i, : min(len(e), width)] = np.frombuffer(e[:width], dtype=np.uint8)
    return out


def unpack_rows(mat: np.ndarray) -> list[str]:
    out = []
    for row in np.asarray(mat):
        s = row.tobytes().decode("utf-8", errors="ignore")
        out.append(" ".join(s.split()))
    return out


def clean_rows(
    rows: list[str], *, strip_html: bool = True, interpret: bool | None = None
) -> list[str]:
    """Clean a list of rows on device.  ``interpret`` defaults to the
    capability check (compiled on TPU, interpret-mode elsewhere) instead of
    unconditionally interpreting."""
    if not rows:
        return []
    if interpret is None:
        interpret = not has_tpu()
    width = max((len(r.encode("utf-8", errors="ignore")) for r in rows), default=1)
    mat = pack_rows(rows, width=_round_up(max(width, 1), LANE_TILE))
    cleaned = text_clean_op(mat, strip_html=strip_html, interpret=interpret)
    return unpack_rows(np.asarray(cleaned))


# Padded-matrix guards for scan_flat: refuse to build a matrix that blows
# the flat buffer up more than 8x (few long rows among many short ones) or
# past 64 MiB — the host scan is cheaper than that much padding traffic.
_MAX_PAD_BYTES = 64 << 20
_MAX_BLOWUP = 8.0
# Same knob as repro.core.engine_config.ENV_PALLAS_INTERPRET; read directly
# here to keep this bridge importable without the core engine layer.
INTERPRET_ENV = "REPRO_PALLAS_INTERPRET"


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _ladder(n: int) -> int:
    """Smallest of 1, 2, 3, 4, 6, 8, 12, 16, ... (``2**k`` and
    ``3 * 2**k``) that is >= ``n``: at most 1.5x padding, and a number of
    distinct values logarithmic in ``n``."""
    p = 1 << max(n - 1, 0).bit_length()
    return p * 3 // 4 if p >= 4 and p * 3 // 4 >= n else p


def padded_shape(n_rows: int, width: int) -> tuple[int, int]:
    """The (rows, width) matrix ``scan_flat`` packs ``n_rows`` rows of at
    most ``width`` bytes into. Widths round up to a power of two (at least
    one lane tile) and rows up a 2**k / 3*2**k ladder of row tiles, then to
    whole row blocks, so a corpus of shards compiles the kernel a handful
    of times instead of once per shard."""
    width_p = max(LANE_TILE, 1 << max(width - 1, 0).bit_length())
    rows_p = _ladder(-(-n_rows // ROW_TILE)) * ROW_TILE
    return _round_up(rows_p, min(block_rows(width_p), rows_p)), width_p


def scan_flat(
    buf: np.ndarray,
    *,
    lower: bool = True,
    strip_html: bool = False,
    strip_parens: bool = False,
    interpret: bool | None = None,
) -> np.ndarray | None:
    """Run a megapass scan pass on device over a flat row buffer.

    Returns the compacted flat result, or ``None`` to decline (caller
    falls back to the byte-identical host scan).  With ``interpret=None``
    the kernel runs compiled on TPU; without a TPU it declines unless
    ``REPRO_PALLAS_INTERPRET`` is set (tests force interpret mode there).
    """
    if interpret is None:
        if has_tpu():
            interpret = False
        elif os.environ.get(INTERPRET_ENV):
            interpret = True
        else:
            return None
    if buf.size == 0 or buf[-1] != 0:
        return None  # rows must be \x00-terminated
    sep = buf == 0
    sep_idx = np.flatnonzero(sep)
    n = sep_idx.size
    starts = np.concatenate(([0], sep_idx[:-1] + 1))
    lens = sep_idx - starts
    width = int(lens.max())
    if width == 0:
        return buf.copy()  # every row empty: nothing to scan
    rows_p, width_p = padded_shape(n, width)  # pad bytes are spaces
    if rows_p * width_p > _MAX_PAD_BYTES or n * width_p > _MAX_BLOWUP * buf.size:
        return None
    row_of = np.cumsum(sep, dtype=np.int64) - sep
    col = np.arange(buf.size, dtype=np.int64) - starts[row_of]
    payload = ~sep
    flat_pos = row_of[payload] * width_p + col[payload]
    mat = np.full(rows_p * width_p, 32, dtype=np.uint8)
    mat[flat_pos] = buf[payload]
    out_mat = np.asarray(
        text_scan_op(
            mat.reshape(rows_p, width_p),
            lower=lower,
            strip_html=strip_html,
            strip_parens=strip_parens,
            interpret=interpret,
        )
    )
    out_flat = np.zeros(buf.size, dtype=np.uint8)
    out_flat[payload] = out_mat.reshape(-1)[flat_pos]
    return out_flat[(out_flat != 0) | sep]
