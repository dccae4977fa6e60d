"""Vectorized byte-level text operations — the columnar execution engine.

This is the TPU-era analogue of Spark's Tungsten columnar execution: every
preprocessing stage is a handful of C-speed vector passes over a *flat*
buffer instead of a Python loop per row (the conventional approach,
Algorithm 2 in the paper).

Flat representation
-------------------
A column of ``n`` strings is stored as a single ``uint8`` array in which rows
are separated by ``ROW_SEP`` (``\\x00``).  Text is treated as ASCII-oriented
UTF-8 (the paper's corpus is English scholarly text); bytes outside
``[a-z ]`` are removed by the unwanted-character LUT anyway.

Op descriptors
--------------
Stages describe themselves as small *ops* (LUT / SPAN / REPLACE / COLLAPSE /
WORDPRED).  The executor (``apply_ops``) runs them; ``fuse_ops`` performs
Catalyst-style adjacent-op fusion:

* ``LUT ∘ LUT``      → one composed 256-entry LUT (one pass instead of two)
* ``WORDPRED | WORDPRED`` → one word-segmentation + hash pass evaluating the
  OR of the predicates (exact: predicates are word-local, so removing words
  in one pass is equivalent to sequential removal)
* adjacent ``COLLAPSE`` ops deduplicate.

Backends: megapass lowering
---------------------------
Beyond adjacent fusion, :func:`compile_megapass` lowers a whole op chain to
a small *pass program* executed by :func:`run_megapass` — the whole-stage
codegen analogue: instead of materializing one intermediate buffer per op,
the chain is segmented into

* **scan passes** — a maximal ``LUT``/``SPAN`` run.  The value LUTs compose
  into one 256-entry table; each span's open/close detection becomes a
  boolean LUT over the *raw* bytes (``composed_lut_so_far == open_byte``),
  and the span masks are made sequential-exact by zeroing every span's
  depth delta at positions an earlier span already deleted.  One gather at
  the end applies the composed LUT and compacts — a single output write
  where the loops backend writes once per op.
* **word passes** — an optional pure-LUT prefix plus a maximal
  ``COLLAPSE``/``WORDPRED`` run.  Words are segmented once, the OR of all
  predicates is evaluated on that one segmentation, and a single keep-mask
  compaction emits surviving words with exactly one space per gap (word
  predicates are word-local and every word-level stage re-collapses, so
  this equals sequential application byte-for-byte).
* **barriers** — ``REPLACE``/``REGEX`` ops change lengths via
  ``bytes.replace``/``re.sub`` and run materialized, exactly as in the
  loops backend.

:func:`execute_ops` dispatches between backends — ``loops`` (one pass per
op, the paper-faithful P3SAPP executor), ``fused`` (megapass), and
``pallas`` (megapass whose scan passes offload to the
``kernels/text_clean`` Pallas kernel when the pass matches the kernel's
shape, falling back to the host scan otherwise; worker processes run its
host form, :func:`worker_backend`).  Selection:  explicit
argument > ``REPRO_BYTES_BACKEND`` env var > ``loops``.  **All backends
are byte-identical by contract**; any chain the megapass compiler cannot
prove exact (e.g. a LUT that remaps the row separator) falls back to
``loops`` wholesale.  Fusion wins are measured in EXPERIMENTS.md §Perf
(data layer) and ``benchmarks/bench_kernels.py``.

Semantics contract (shared with the row-wise oracles in ``stages.py``)
----------------------------------------------------------------------
* HTML tags and parentheses are balanced and non-nested within each row
  (the corpus generator guarantees this; the span mask resets its depth at
  every row separator so malformed rows can never swallow a separator).
* ``\\x00`` never appears inside a row (ingestion strips it).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Callable, Sequence

import numpy as np

ROW_SEP = 0
SPACE = 32

# ---------------------------------------------------------------------------
# flatten / unflatten
# ---------------------------------------------------------------------------


def flatten(rows: Sequence[str]) -> np.ndarray:
    """Join rows with ROW_SEP into one uint8 buffer (trailing sep included)."""
    joined = ("\x00".join(rows) + "\x00").encode("utf-8", errors="ignore") if len(rows) else b""
    return np.frombuffer(joined, dtype=np.uint8).copy()


def unflatten(buf: np.ndarray) -> list[str]:
    """Inverse of :func:`flatten`."""
    if buf.size == 0:
        return []
    parts = buf.tobytes().split(b"\x00")
    if parts and parts[-1] == b"":
        parts = parts[:-1]
    return [p.decode("utf-8", errors="ignore") for p in parts]


def n_rows(buf: np.ndarray) -> int:
    return int((buf == ROW_SEP).sum())


# ---------------------------------------------------------------------------
# Lookup tables
# ---------------------------------------------------------------------------

LOWER_LUT = np.arange(256, dtype=np.uint8)
LOWER_LUT[ord("A") : ord("Z") + 1] += 32

# RemoveUnwantedCharacters: keep [a-z], space, ROW_SEP; everything else
# (digits, punctuation, specials, residual uppercase, UTF-8 >127) → space.
UNWANTED_LUT = np.full(256, SPACE, dtype=np.uint8)
UNWANTED_LUT[ord("a") : ord("z") + 1] = np.arange(ord("a"), ord("z") + 1, dtype=np.uint8)
UNWANTED_LUT[SPACE] = SPACE
UNWANTED_LUT[ROW_SEP] = ROW_SEP


# Contraction mapping: applied on flat bytes after lowercasing, before
# punctuation stripping; each entry is one C-speed ``bytes.replace`` pass.
CONTRACTIONS: tuple[tuple[bytes, bytes], ...] = (
    (b"won't", b"will not"),
    (b"can't", b"can not"),
    (b"shan't", b"shall not"),
    (b"n't", b" not"),
    (b"'re", b" are"),
    (b"'ve", b" have"),
    (b"'ll", b" will"),
    (b"'m", b" am"),
    (b"'d", b" would"),
    (b"'s", b""),
    (b"'", b""),
)


# ---------------------------------------------------------------------------
# Character-level passes
# ---------------------------------------------------------------------------


def apply_lut(buf: np.ndarray, lut: np.ndarray) -> np.ndarray:
    return lut[buf]


def span_strip(buf: np.ndarray, open_b: int, close_b: int) -> np.ndarray:
    """Delete ``open .. close`` spans (both delimiters included).

    Depth resets at every row separator (fast path when rows are balanced).
    """
    opens = buf == open_b
    closes = buf == close_b
    delta = np.subtract(opens, closes, dtype=np.int8)
    depth = np.cumsum(delta, dtype=np.int32)
    sep = buf == ROW_SEP
    sep_depths = depth[sep]
    if sep_depths.size and sep_depths.any():  # malformed rows: per-row reset
        row_id = np.cumsum(sep, dtype=np.int32) - sep
        start_depth = np.concatenate(([0], sep_depths)).astype(np.int32)[row_id]
        inside = (depth - start_depth) > 0
    else:
        inside = depth > 0  # includes opener, excludes closer
    keep = ~(inside | closes) | sep
    return buf[keep]


def replace_patterns(buf: np.ndarray, patterns: Sequence[tuple[bytes, bytes]]) -> np.ndarray:
    raw = buf.tobytes()
    for pat, rep in patterns:
        raw = raw.replace(pat, rep)
    return np.frombuffer(raw, dtype=np.uint8).copy()


def expand_contractions(buf: np.ndarray) -> np.ndarray:
    return replace_patterns(buf, CONTRACTIONS)


def collapse_spaces(buf: np.ndarray) -> np.ndarray:
    """Collapse space runs; strip leading/trailing spaces of each row."""
    if buf.size == 0:
        return buf
    sp = buf == SPACE
    sep = buf == ROW_SEP
    prev_sp_or_start = np.empty_like(sp)
    prev_sp_or_start[0] = True
    prev_sp_or_start[1:] = sp[:-1] | sep[:-1]
    buf2 = buf[~(sp & prev_sp_or_start)]
    sp2 = buf2 == SPACE
    next_sep = np.empty_like(sp2)
    next_sep[-1] = True
    next_sep[:-1] = buf2[1:] == ROW_SEP
    return buf2[~(sp2 & next_sep)]


def regex_sub(buf: np.ndarray, pattern: bytes, repl: bytes) -> np.ndarray:
    """One compiled-regex substitution pass over the flat bytes.

    Row-local as long as no match touches ``\\x00`` (the row separator).
    Construction-time probing (:func:`regex_op`) rejects the common
    separator-matching patterns (``.``, ``\\W``, ``[^a-z]``, …), and the
    row count is re-verified here — exact enforcement, since a match that
    crossed a separator would have to consume it."""
    import re

    raw = buf.tobytes()
    out = re.sub(pattern, repl, raw)
    if out.count(b"\x00") != raw.count(b"\x00"):
        raise ValueError(
            f"regex_replace({pattern.decode(errors='replace')!r}) matched the "
            "row separator and would merge or split rows; exclude NUL from "
            "the pattern (e.g. use [^a-z\\x01-\\x1f] style classes)"
        )
    return np.frombuffer(out, dtype=np.uint8).copy()


# ---------------------------------------------------------------------------
# Row-level reductions (predicates over flat buffers; no decode)
# ---------------------------------------------------------------------------


def row_lengths(buf: np.ndarray) -> np.ndarray:
    """Per-row byte length *including* the trailing separator."""
    sep_idx = np.flatnonzero(buf == ROW_SEP)
    return np.diff(np.concatenate(([-1], sep_idx))).astype(np.int64)


def row_nonempty(buf: np.ndarray) -> np.ndarray:
    """Boolean mask of rows with at least one byte of payload."""
    return row_lengths(buf) > 1


def row_word_counts(buf: np.ndarray) -> np.ndarray:
    """Per-row number of space-separated words (vectorized, no decode)."""
    n = n_rows(buf)
    counts = np.zeros(n, dtype=np.int64)
    if buf.size == 0:
        return counts
    sep = buf == ROW_SEP
    _, _, start_idx, _ = _segment_words(buf)
    if start_idx.size:
        row_of_byte = np.cumsum(sep, dtype=np.int64) - sep
        np.add.at(counts, row_of_byte[start_idx], 1)
    return counts


def rows_containing(buf: np.ndarray, needle: bytes) -> np.ndarray:
    """Boolean mask of rows whose payload contains ``needle`` (a literal
    byte string without ``\\x00``, so a match can never span rows)."""
    n = n_rows(buf)
    mask = np.zeros(n, dtype=bool)
    if not needle or buf.size == 0:
        mask[:] = bool(n) and not needle
        return mask
    m = len(needle)
    if m > buf.size:
        return mask
    pat = np.frombuffer(needle, dtype=np.uint8)
    hit = buf[: buf.size - m + 1] == pat[0]
    for j in range(1, m):
        hit &= buf[j : buf.size - m + 1 + j] == pat[j]
    pos = np.flatnonzero(hit)
    if pos.size:
        sep = buf == ROW_SEP
        row_of_byte = np.cumsum(sep, dtype=np.int64) - sep
        mask[row_of_byte[pos]] = True
    return mask


def concat_rows(bufs: Sequence[np.ndarray], sep: bytes = b" ") -> np.ndarray:
    """Row-wise concatenation of equal-row-count flat buffers with ``sep``
    between the parts (byte-level; rows never decode to str)."""
    if not bufs:
        raise ValueError("concat_rows needs at least one buffer")
    split = [b.tobytes().split(b"\x00")[:-1] for b in bufs]
    counts = {len(rows) for rows in split}
    if len(counts) > 1:
        raise ValueError(f"ragged concat inputs: row counts {sorted(counts)}")
    joined = b"".join(sep.join(parts) + b"\x00" for parts in zip(*split))
    return np.frombuffer(joined, dtype=np.uint8).copy()


# ---------------------------------------------------------------------------
# Word-level passes (segmented vector ops, no per-word Python)
# ---------------------------------------------------------------------------


def _segment_words(buf: np.ndarray):
    """Return (is_word_byte, word_id_per_byte, start_idx, lengths)."""
    delim = (buf == SPACE) | (buf == ROW_SEP)
    isw = ~delim
    starts = isw.copy()
    starts[1:] &= delim[:-1]
    start_idx = np.flatnonzero(starts)
    wid = np.cumsum(starts, dtype=np.int32) - 1  # valid where isw
    if start_idx.size:
        lengths = np.add.reduceat(isw.astype(np.int32), start_idx)
    else:
        lengths = np.zeros(0, dtype=np.int32)
    return isw, wid, start_idx, lengths


class WordView:
    """Lazy per-word key view. ``k1``/``k2`` pack bytes 0-7 / 8-15 of each
    word (zero padded), so (k1, k2, length) identifies any word of <=16
    bytes *exactly* — no hash collisions. Words longer than 16 bytes cannot
    equal any dictionary word of <=16 bytes (length check)."""

    def __init__(self, buf: np.ndarray, start_idx: np.ndarray, lengths: np.ndarray):
        self._buf = buf
        self.start_idx = start_idx
        self.lengths = lengths
        self._k1: np.ndarray | None = None
        self._k2: np.ndarray | None = None

    def _pack(self, offset: int, subset: np.ndarray | None = None) -> np.ndarray:
        starts = self.start_idx if subset is None else self.start_idx[subset]
        lens = self.lengths if subset is None else self.lengths[subset]
        pad = np.zeros(8, dtype=np.uint8)
        bufp = np.concatenate([self._buf, pad])
        cols = np.arange(8, dtype=np.int64)
        mat = bufp[starts[:, None] + (offset + cols)[None, :]]
        mat[cols[None, :] >= (lens[:, None] - offset)] = 0
        return mat.reshape(-1).view(np.uint64)

    @property
    def k1(self) -> np.ndarray:
        if self._k1 is None:
            self._k1 = self._pack(0)
        return self._k1

    @property
    def k2(self) -> np.ndarray:
        if self._k2 is None:
            long = np.flatnonzero(self.lengths > 8)
            k2 = np.zeros(self.start_idx.size, dtype=np.uint64)
            if long.size:
                k2[long] = self._pack(8, subset=long)
            self._k2 = k2
        return self._k2


def pack_word(word: str) -> tuple[int, int, int]:
    """(k1, k2, length) key of a dictionary word (must be <=16 bytes)."""
    b = word.encode("utf-8")
    if len(b) > 16:
        raise ValueError(f"dictionary word too long: {word!r}")
    padded = b + b"\x00" * (16 - len(b))
    k = np.frombuffer(padded, dtype=np.uint64)
    return int(k[0]), int(k[1]), len(b)


class WordSet:
    """Sorted exact-match set of <=16-byte words (e.g. stopwords)."""

    def __init__(self, words: Sequence[str]):
        keys = sorted({pack_word(w) for w in words})
        self.k1 = np.array([k[0] for k in keys], dtype=np.uint64)
        self.k2 = np.array([k[1] for k in keys], dtype=np.uint64)
        self.ln = np.array([k[2] for k in keys], dtype=np.int32)
        self._max_dup = self._compute_max_dup()

    def contains(self, view: WordView) -> np.ndarray:
        if self.k1.size == 0 or view.start_idx.size == 0:
            return np.zeros(view.start_idx.size, dtype=bool)
        k1 = view.k1
        pos = np.searchsorted(self.k1, k1)
        # self.k1 can contain duplicates (same first-8 bytes, different tail);
        # check up to 2 candidate slots — enough for English stopword lists,
        # asserted at construction time below.
        hit = np.zeros(k1.size, dtype=bool)
        for off in range(self._max_dup):
            p = np.clip(pos + off, 0, self.k1.size - 1)
            hit |= (
                (self.k1[p] == k1)
                & (self.k2[p] == view.k2)
                & (self.ln[p] == view.lengths)
            )
        return hit

    def signature(self) -> bytes:
        """Stable content signature (for plan fingerprinting)."""
        return b"wordset:" + self.k1.tobytes() + self.k2.tobytes() + self.ln.tobytes()

    def _compute_max_dup(self) -> int:
        if self.k1.size < 2:
            return 1
        runs = 1
        best = 1
        for i in range(1, self.k1.size):
            runs = runs + 1 if self.k1[i] == self.k1[i - 1] else 1
            best = max(best, runs)
        return best


def remove_words(
    buf: np.ndarray,
    bad_fn: Callable[[WordView | None, np.ndarray], np.ndarray],
    needs_hashes: bool = True,
) -> np.ndarray:
    """Delete words flagged by ``bad_fn(word_view|None, lengths)``."""
    # Word-level stages always normalize whitespace (Spark operates on token
    # arrays; our textual form rejoins with single spaces) — so the no-op
    # paths still collapse.
    isw, wid, start_idx, lengths = _segment_words(buf)
    if start_idx.size == 0:
        return collapse_spaces(buf)
    view = WordView(buf, start_idx, lengths) if needs_hashes else None
    bad = bad_fn(view, lengths)
    if not bad.any():
        return collapse_spaces(buf)
    kill = np.zeros(buf.size, dtype=bool)
    w = np.clip(wid, 0, None)
    kill[isw] = bad[w[isw]]
    return collapse_spaces(buf[~kill])


def remove_short_words(buf: np.ndarray, threshold: int) -> np.ndarray:
    return remove_words(buf, lambda v, ln: ln <= threshold, needs_hashes=False)


def remove_stopwords(buf: np.ndarray, stopwords: "WordSet") -> np.ndarray:
    return remove_words(buf, lambda v, ln: stopwords.contains(v))


# ---------------------------------------------------------------------------
# Op descriptors + fusing executor (Catalyst-style plan optimization)
# ---------------------------------------------------------------------------


@dataclass(frozen=True, eq=False)
class Op:
    kind: str  # "lut" | "span" | "replace" | "collapse" | "wordpred" | "regex"
    lut: np.ndarray | None = None
    span: tuple[int, int] | None = None
    patterns: tuple[tuple[bytes, bytes], ...] | None = None
    pred: Callable | None = None  # (hashes|None, lengths) -> bool[n_words]
    needs_hashes: bool = False
    regex: tuple[bytes, bytes] | None = None  # (pattern, repl)


# Module-level predicates (picklable for the process-pool executor).


def pred_short(view, ln, threshold: int):
    return ln <= threshold


def pred_stopword(view, ln, words: "WordSet"):
    return words.contains(view)


def pred_or(view, ln, p1, p2):
    return p1(view, ln) | p2(view, ln)


def lut_op(lut: np.ndarray) -> Op:
    return Op("lut", lut=lut)


def span_op(open_c: str, close_c: str) -> Op:
    return Op("span", span=(ord(open_c), ord(close_c)))


def replace_op(patterns: Sequence[tuple[bytes, bytes]]) -> Op:
    return Op("replace", patterns=tuple(patterns))


def collapse_op() -> Op:
    return Op("collapse")


def wordpred_op(pred: Callable, needs_hashes: bool) -> Op:
    return Op("wordpred", pred=pred, needs_hashes=needs_hashes)


def regex_op(pattern: str, repl: str) -> Op:
    """Regex substitution op. The pattern must compile, must not be able to
    match the row separator, and the replacement must not introduce one —
    otherwise a substitution could merge or split rows. Probing here
    catches the common separator-matchers (``.``, ``\\W``, ``[^...]``
    classes) at plan-build time; :func:`regex_sub` re-verifies the row
    count at execution, so exotic patterns that slip past the probes still
    fail loudly instead of corrupting rows."""
    import re

    pat = pattern.encode("utf-8")
    rep = repl.encode("utf-8")
    rx = re.compile(pat)  # fail fast on bad patterns, at plan-build time
    if b"\x00" in rep:
        raise ValueError("regex replacement must not emit NUL (the row separator)")
    for probe in (b"\x00", b"a\x00", b"\x00a", b"ab\x00cd"):
        if any(b"\x00" in m.group() for m in rx.finditer(probe)):
            raise ValueError(
                f"regex pattern {pattern!r} can match NUL (the row separator) "
                "and would merge or split rows; exclude \\x00 explicitly"
            )
    return Op("regex", regex=(pat, rep))


def apply_op(buf: np.ndarray, op: Op) -> np.ndarray:
    if op.kind == "lut":
        return apply_lut(buf, op.lut)
    if op.kind == "span":
        return span_strip(buf, *op.span)
    if op.kind == "replace":
        return replace_patterns(buf, op.patterns)
    if op.kind == "collapse":
        return collapse_spaces(buf)
    if op.kind == "wordpred":
        return remove_words(buf, op.pred, needs_hashes=op.needs_hashes)
    if op.kind == "regex":
        return regex_sub(buf, *op.regex)
    raise ValueError(f"unknown op {op.kind}")


def apply_ops(buf: np.ndarray, ops: Sequence[Op]) -> np.ndarray:
    for op in ops:
        buf = apply_op(buf, op)
    return buf


class UnfingerprintableOpError(ValueError):
    """The op's behavior cannot be captured in a stable signature (e.g. a
    lambda predicate): callers must treat its outputs as uncacheable
    rather than risk serving stale results under a colliding key."""


def _pred_signature(pred) -> bytes:
    """Stable byte signature of a word predicate (module-level function or a
    ``functools.partial`` tree over them) — the cache key must change when any
    parameter (threshold, stopword list, …) changes."""
    import functools

    if isinstance(pred, functools.partial):
        parts = [b"partial:", _pred_signature(pred.func)]
        for a in pred.args:
            parts.append(_value_signature(a))
        for k in sorted(pred.keywords):
            parts.append(k.encode() + b"=" + _value_signature(pred.keywords[k]))
        return b"|".join(parts)
    qualname = getattr(pred, "__qualname__", None)
    if qualname is None or "<lambda>" in qualname or "<locals>" in qualname:
        # Lambdas / closures all share a qualname; two different ones must
        # never produce the same fingerprint.
        raise UnfingerprintableOpError(
            f"cannot fingerprint predicate {pred!r}; use a module-level "
            "function (optionally via functools.partial) to make it cacheable"
        )
    module = getattr(pred, "__module__", "") or ""
    parts = [f"{module}.{qualname}".encode()]
    code = getattr(pred, "__code__", None)
    if code is not None:
        # Include the bytecode so *editing the function body* invalidates
        # cached results, not just renaming it.
        parts.append(code.co_code)
        parts.append(
            repr([c for c in code.co_consts if not hasattr(c, "co_code")]).encode()
        )
    return b"\x1f".join(parts)


def _value_signature(value) -> bytes:
    """Deterministic, collision-averse signature of a predicate parameter.

    repr() is not good enough here: set iteration order varies per process
    (hash randomization → a cache that never hits across runs) and custom
    reprs may omit the parameters that matter (→ stale hits). Anything we
    cannot serialize deterministically raises, poisoning the column into
    the uncacheable-but-correct path."""
    if isinstance(value, WordSet):
        return value.signature()
    if callable(value):
        return _pred_signature(value)
    if isinstance(value, np.ndarray):
        return b"nd:" + value.tobytes()
    if value is None or isinstance(value, (bool, int, float, str, bytes)):
        return f"{type(value).__name__}:{value!r}".encode()
    if isinstance(value, (tuple, list)):
        return b"seq:" + b",".join(_value_signature(v) for v in value)
    if isinstance(value, (set, frozenset)):
        return b"set:" + b",".join(sorted(_value_signature(v) for v in value))
    if isinstance(value, dict):
        return b"map:" + b",".join(
            _value_signature(k) + b"=" + _value_signature(v)
            for k, v in sorted(value.items(), key=lambda kv: repr(kv[0]))
        )
    raise UnfingerprintableOpError(
        f"cannot fingerprint predicate parameter {value!r} "
        f"({type(value).__name__}); pass plain data or a WordSet"
    )


def op_signature(op: Op) -> bytes:
    """Stable byte signature of one op — the unit of plan fingerprinting."""
    if op.kind == "lut":
        return b"lut:" + op.lut.tobytes()
    if op.kind == "span":
        return b"span:%d,%d" % op.span
    if op.kind == "replace":
        # Length-prefix each side: joining with separators would let two
        # different pattern lists collide into one signature (e.g. a
        # pattern containing the separator), which the cache must never do.
        parts = [b"replace:"]
        for p, r in op.patterns:
            parts.append(len(p).to_bytes(4, "little") + p)
            parts.append(len(r).to_bytes(4, "little") + r)
        return b"".join(parts)
    if op.kind == "collapse":
        return b"collapse"
    if op.kind == "wordpred":
        return b"wordpred:" + _pred_signature(op.pred)
    if op.kind == "regex":
        pat, rep = op.regex
        return (
            b"regex:"
            + len(pat).to_bytes(4, "little") + pat
            + len(rep).to_bytes(4, "little") + rep
        )
    raise ValueError(f"unknown op {op.kind}")


def ops_fingerprint(ops: Sequence[Op]) -> str:
    """Hex fingerprint of an op chain (order-sensitive, parameter-exact)."""
    import hashlib

    h = hashlib.blake2b(digest_size=16)
    for op in ops:
        sig = op_signature(op)
        h.update(len(sig).to_bytes(8, "little"))
        h.update(sig)
    return h.hexdigest()


def fuse_ops(ops: Sequence[Op]) -> list[Op]:
    """Adjacent-op fusion. Exact: see module docstring."""
    fused: list[Op] = []
    for op in ops:
        prev = fused[-1] if fused else None
        if prev is not None and prev.kind == op.kind == "lut":
            fused[-1] = lut_op(op.lut[prev.lut])
        elif prev is not None and prev.kind == op.kind == "collapse":
            pass  # idempotent
        elif prev is not None and prev.kind == op.kind == "wordpred":
            from functools import partial

            fused[-1] = wordpred_op(
                partial(pred_or, p1=prev.pred, p2=op.pred),
                prev.needs_hashes or op.needs_hashes,
            )
        else:
            fused.append(op)
    return fused


# ---------------------------------------------------------------------------
# Megapass backend: whole-chain lowering to single-sweep pass programs
# ---------------------------------------------------------------------------

BACKENDS = ("loops", "fused", "pallas")
BACKEND_ENV = "REPRO_BYTES_BACKEND"

_IDENTITY_LUT = np.arange(256, dtype=np.uint8)


def resolve_backend(backend: str | None = None) -> str:
    """Backend selection: explicit argument > REPRO_BYTES_BACKEND > loops."""
    b = backend or os.environ.get(BACKEND_ENV, "") or "loops"
    if b not in BACKENDS:
        raise ValueError(f"unknown bytes backend {b!r}; expected one of {BACKENDS}")
    return b


def worker_backend(backend: str) -> str:
    """The backend an out-of-process worker runs for ``backend``: the
    device belongs to the parent, so ``pallas`` becomes its host form,
    ``fused`` (the same megapass with the host scan, byte-identical)."""
    return "fused" if backend == "pallas" else backend


@dataclass(frozen=True)
class ScanPass:
    """A maximal LUT/SPAN run lowered to one sweep + one compaction.

    ``lut`` is the full composed value LUT of the run; ``spans`` holds one
    detection pair per span op describing open/close positions in terms of
    the *raw* bytes (``composed_lut_at_that_point == delimiter``), so no
    intermediate values materialize.  Each detector is either a plain byte
    value (the delimiter's preimage under the composed LUT is a single
    byte — one vector compare) or a 256-entry boolean LUT (general case).
    ``pairs`` keeps the mapped (open, close) byte values for the Pallas
    eligibility check."""

    lut: np.ndarray
    spans: tuple[tuple[object, object], ...]
    pairs: tuple[tuple[int, int], ...]


@dataclass(frozen=True)
class WordPass:
    """An optional pure-LUT prefix + a maximal COLLAPSE/WORDPRED run: one
    segmentation, OR of all predicates, one keep-mask compaction whose
    output is fully collapsed (every word-level stage re-collapses)."""

    lut: np.ndarray | None
    preds: tuple[tuple[Callable, bool], ...]  # (pred, needs_hashes)


def _sep_safe(lut: np.ndarray) -> bool:
    """True iff ``lut`` maps ROW_SEP to ROW_SEP and nothing else to it —
    the condition under which separator positions in the raw buffer equal
    separator positions in the mapped values (required wherever the fused
    program consults row structure)."""
    return bool(lut[ROW_SEP] == ROW_SEP and not (lut[1:] == ROW_SEP).any())


def _compose_luts(ops: Sequence[Op]) -> np.ndarray:
    lut = _IDENTITY_LUT
    for op in ops:
        lut = op.lut[lut]
    return lut


def _detector(cur: np.ndarray, byte: int):
    """Raw-byte detector for ``composed_lut[raw] == byte``: the preimage
    byte itself when unique (vector compare at run time), else the boolean
    LUT (gather)."""
    pre = np.flatnonzero(cur == byte)
    if pre.size == 1:
        return int(pre[0])
    return cur == byte


def _detect(buf: np.ndarray, det) -> np.ndarray:
    if isinstance(det, np.ndarray):
        return det[buf]
    return buf == det


def _compile_scan(run: Sequence[Op]) -> ScanPass | None:
    cur = _IDENTITY_LUT
    spans: list[tuple[object, object]] = []
    pairs: list[tuple[int, int]] = []
    for op in run:
        if op.kind == "lut":
            cur = op.lut[cur]
        else:
            open_b, close_b = op.span
            # Span detection consults row structure (per-row depth reset)
            # and delimiter identity; bail to the loops backend on the
            # degenerate shapes where raw-byte detection is not exact.
            if not _sep_safe(cur) or ROW_SEP in (open_b, close_b) or open_b == close_b:
                return None
            spans.append((_detector(cur, open_b), _detector(cur, close_b)))
            pairs.append((open_b, close_b))
    return ScanPass(lut=cur, spans=tuple(spans), pairs=tuple(pairs))


def compile_megapass(ops: Sequence[Op]) -> list[tuple[str, object]] | None:
    """Lower an op chain to a pass program: ``[("scan", ScanPass) |
    ("word", WordPass) | ("op", Op), ...]``.  Returns ``None`` when any
    segment cannot be proven byte-identical to sequential execution —
    callers then fall back to :func:`apply_ops`."""
    ops = list(ops)
    passes: list[tuple[str, object]] = []
    i, n = 0, len(ops)
    while i < n:
        kind = ops[i].kind
        if kind in ("replace", "regex"):
            passes.append(("op", ops[i]))
            i += 1
            continue
        head_lut: np.ndarray | None = None
        if kind in ("lut", "span"):
            j = i
            while j < n and ops[j].kind in ("lut", "span"):
                j += 1
            # A trailing pure-LUT suffix feeds the following word pass (so
            # e.g. [unwanted-LUT, collapse, wordpred] is ONE pass, not two).
            t = j
            if j < n and ops[j].kind in ("collapse", "wordpred"):
                while t > i and ops[t - 1].kind == "lut":
                    t -= 1
            if t > i:
                scan = _compile_scan(ops[i:t])
                if scan is None:
                    return None
                passes.append(("scan", scan))
            if t < j:
                head_lut = _compose_luts(ops[t:j])
                if not _sep_safe(head_lut):
                    return None
            i = j
            if head_lut is None:
                continue
        if i < n and ops[i].kind in ("collapse", "wordpred"):
            j = i
            while j < n and ops[j].kind in ("collapse", "wordpred"):
                j += 1
            preds = tuple(
                (op.pred, op.needs_hashes) for op in ops[i:j] if op.kind == "wordpred"
            )
            passes.append(("word", WordPass(lut=head_lut, preds=preds)))
            i = j
            continue
        if head_lut is not None:  # pragma: no cover - unreachable by construction
            return None
        return None  # unknown op kind
    return passes


def _run_scan(buf: np.ndarray, sp: ScanPass) -> np.ndarray:
    """One sweep for a LUT/SPAN run.  Span masking is *sparse*: delimiter
    bytes are rare in real text, so depths are computed on the hit list
    (O(hits)) and dead byte ranges scattered into the keep mask — the
    full-buffer work is two compares and one flatnonzero per span instead
    of an O(n) cumsum.  Semantics match iterated :func:`span_strip`
    exactly: row-local depth (reset at every separator), any byte at
    positive depth dies, every close byte dies, spans already deleted by
    an earlier span op neither open, close, nor count."""
    identity = sp.lut is _IDENTITY_LUT
    if buf.size == 0 or not sp.spans:
        return buf if identity else sp.lut[buf]
    sep_idx = np.flatnonzero(buf == ROW_SEP)
    alive = np.ones(buf.size, dtype=bool)
    for open_det, close_det in sp.spans:
        opens = _detect(buf, open_det)
        closes = _detect(buf, close_det)
        np.logical_or(opens, closes, out=opens)
        hits = np.flatnonzero(opens)
        if hits.size:
            live = alive[hits]
            if not live.all():
                hits = hits[live]
        if hits.size == 0:
            continue
        is_close = closes[hits]
        sign = np.where(is_close, np.int32(-1), np.int32(1))
        g = np.cumsum(sign)
        rows_h = np.searchsorted(sep_idx, hits)  # hit's row (sep_idx entry = row end)
        first = np.ones(hits.size, dtype=bool)
        first[1:] = rows_h[1:] != rows_h[:-1]
        fpos = np.flatnonzero(first)
        counts = np.diff(np.append(fpos, hits.size))
        d = g - np.repeat((g - sign)[fpos], counts)  # row-local inclusive depth
        if sep_idx.size:
            row_end = np.where(
                rows_h < sep_idx.size,
                sep_idx[np.minimum(rows_h, sep_idx.size - 1)],
                buf.size,
            )
        else:
            row_end = np.full(hits.size, buf.size, dtype=np.int64)
        nxt = np.empty_like(hits)
        nxt[:-1] = hits[1:]
        nxt[-1] = buf.size
        end = np.minimum(nxt, row_end)
        inside = d > 0
        dead = inside | is_close
        # A byte at positive depth kills everything up to the next hit (or
        # row end — unclosed spans swallow the rest of the row, never the
        # separator); a stray close at depth <= 0 kills only itself.
        lens = np.where(inside, end - hits, 1)[dead]
        alive[_span_indices(hits[dead], lens)] = False
    out = buf[alive]
    return out if identity else sp.lut[out]


def _pallas_scan_args(sp: ScanPass) -> dict | None:
    """Kernel-shape check for a scan pass: composed LUT is identity or
    lowercasing, spans are the canonical ``<>`` / ``()`` prefix (in that
    order), and each span's detection LUT is exactly what the kernel
    computes (``final_lut[raw] == delimiter``)."""
    if np.array_equal(sp.lut, LOWER_LUT):
        lower = True
    elif np.array_equal(sp.lut, _IDENTITY_LUT):
        lower = False
    else:
        return None
    allowed = ((ord("<"), ord(">")), (ord("("), ord(")")))
    if sp.pairs not in (allowed[:1], allowed[1:], allowed, ()):
        return None

    def det_array(det):
        return det if isinstance(det, np.ndarray) else _IDENTITY_LUT == det

    for (open_b, close_b), (open_det, close_det) in zip(sp.pairs, sp.spans):
        if not np.array_equal(det_array(open_det), sp.lut == open_b):
            return None
        if not np.array_equal(det_array(close_det), sp.lut == close_b):
            return None
    return {
        "lower": lower,
        "strip_html": allowed[0] in sp.pairs,
        "strip_parens": allowed[1] in sp.pairs,
    }


def _run_scan_pallas(
    buf: np.ndarray, sp: ScanPass, stats: dict | None = None
) -> np.ndarray:
    """Offload a scan pass to the Pallas text-scan kernel when it matches
    the kernel's shape; the byte-identical host scan otherwise.

    Only the process that owns the device runs this: out-of-process
    workers (forked pools, remote TCP workers) are handed
    :func:`worker_backend` by their parent and never reach jax. ``stats``
    counts ``pallas_calls`` (the kernel produced the bytes) and
    ``pallas_declines`` (the bridge refused and the host scan ran)."""
    kwargs = _pallas_scan_args(sp)
    if kwargs is None or not sp.spans or buf.size == 0:
        return _run_scan(buf, sp)  # pure-LUT passes don't pay padding traffic
    try:
        from repro.kernels.text_clean.ops import scan_flat
    except ImportError:  # jax is not installed
        out = None
    else:
        out = scan_flat(buf, **kwargs)
    if stats is not None:
        key = "pallas_declines" if out is None else "pallas_calls"
        stats[key] = stats.get(key, 0) + 1
    return _run_scan(buf, sp) if out is None else out


def _span_indices(starts: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """Flat indices covering ``[starts[k], starts[k]+lens[k])`` for all k —
    O(total span bytes), no Python loop."""
    total = int(lens.sum())
    cum = np.cumsum(lens) - lens
    return np.repeat(starts - cum, lens) + np.arange(total, dtype=np.int64)


def _run_word(buf: np.ndarray, wp: WordPass) -> np.ndarray:
    if buf.size == 0:
        return buf
    lut = wp.lut
    needs = any(nh for _, nh in wp.preds)
    # Word content is only consulted by hash-based predicates; otherwise
    # detection runs via boolean LUTs over the raw bytes and the value LUT
    # applies once, after compaction, to the (smaller) output.
    vals = buf if lut is None else (lut[buf] if needs else None)
    if vals is not None:
        sep = vals == ROW_SEP
        delim = sep | (vals == SPACE)
    else:
        sep = buf == ROW_SEP  # lut is sep-safe (checked at compile time)
        delim = (lut == SPACE)[buf] | sep
    isw = ~delim
    starts = isw.copy()
    starts[1:] &= delim[:-1]
    start_idx = np.flatnonzero(starts)
    if start_idx.size == 0:  # no words: a collapsed row is empty
        out = (buf if vals is None else vals)[sep]
        return out  # ROW_SEP is lut-invariant, so no final map needed
    lengths = np.add.reduceat(isw.astype(np.int32), start_idx)
    bad = np.zeros(start_idx.size, dtype=bool)
    if wp.preds:
        view = WordView(vals, start_idx, lengths) if needs else None
        for pred, _nh in wp.preds:
            bad |= pred(view, lengths)
    if bad.any():
        keep = isw
        keep[_span_indices(start_idx[bad], lengths[bad])] = False
        good = ~bad
        good_starts = start_idx[good]
        good_lens = lengths[good]
    else:
        keep = isw
        good_starts = start_idx
        good_lens = lengths
    # Collapse: emit exactly one space per gap between consecutive
    # surviving words of a row — the byte right after a surviving word's
    # end is always a (mapped) space when another word follows in the same
    # row, and all of a gap's space bytes map to the same output byte, so
    # keeping this one is byte-identical to sequential collapse.
    sep_idx = np.flatnonzero(sep)
    rows_g = np.searchsorted(sep_idx, good_starts)
    if good_starts.size > 1:
        not_last = np.empty(good_starts.size, dtype=bool)
        not_last[:-1] = rows_g[:-1] == rows_g[1:]
        not_last[-1] = False
        keep[good_starts[not_last] + good_lens[not_last]] = True
    keep |= sep
    out = (buf if vals is None else vals)[keep]
    return out if vals is not None or lut is None else lut[out]


def run_megapass(
    buf: np.ndarray,
    passes: Sequence[tuple[str, object]],
    *,
    pallas: bool = False,
    stats: dict | None = None,
) -> np.ndarray:
    for kind, p in passes:
        if kind == "scan":
            buf = _run_scan_pallas(buf, p, stats) if pallas else _run_scan(buf, p)
        elif kind == "word":
            buf = _run_word(buf, p)
        else:
            buf = apply_op(buf, p)
    return buf


# compile_megapass is cheap but runs once per shard x column; memoize by op
# identity (ops are built once at plan-compile time and live as long as the
# program).  Holding the ops tuple keeps the ids stable — a live object can
# never share an id with a cached one.
_MEGAPASS_CACHE: dict[tuple[int, ...], tuple[tuple[Op, ...], object]] = {}


def _compile_cached(ops: Sequence[Op]):
    key = tuple(id(op) for op in ops)
    hit = _MEGAPASS_CACHE.get(key)
    if hit is not None:
        return hit[1]
    prog = compile_megapass(ops)
    if len(_MEGAPASS_CACHE) >= 128:
        _MEGAPASS_CACHE.clear()
    _MEGAPASS_CACHE[key] = (tuple(ops), prog)
    return prog


def execute_ops(
    buf: np.ndarray,
    ops: Sequence[Op],
    backend: str | None = None,
    *,
    stats: dict | None = None,
) -> np.ndarray:
    """Run an op chain under the selected backend (see module docstring).

    Byte-identical across backends; chains the megapass compiler cannot
    prove exact fall back to the loops backend wholesale. ``stats``, when
    given, counts the ``pallas`` backend's kernel calls and declines."""
    b = resolve_backend(backend)
    if b == "loops" or not ops:
        return apply_ops(buf, ops)
    prog = _compile_cached(ops)
    if prog is None:
        return apply_ops(buf, ops)
    return run_megapass(buf, prog, pallas=(b == "pallas"), stats=stats)
