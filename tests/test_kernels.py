"""Pallas kernel validation: interpret-mode vs pure-jnp oracle, sweeping
shapes and dtypes.

Every parity test here runs under ``interpret=True`` so the kernel bodies
execute on CPU in plain CI — no blanket skip. The only genuinely-TPU-only
cases are the *compiled* (non-interpret) runs, and those take the ``tpu``
fixture, which asks for the backend when the test runs — never while the
module is collected."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention_op
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.lstm_cell.ops import lstm_cell_op
from repro.kernels.lstm_cell.ref import lstm_cell_ref
from repro.kernels.rg_lru.ops import rg_lru_op
from repro.kernels.rg_lru.ref import rg_lru_ref
from repro.kernels.text_clean.ops import clean_rows, pack_rows, text_clean_op
from repro.kernels.text_clean.ref import text_clean_ref

KEY = jax.random.PRNGKey(0)


@pytest.fixture
def tpu():
    """Skip unless JAX's backend is a TPU (compiled Mosaic kernels)."""
    from repro.kernels.pallas_compat import has_tpu

    if not has_tpu():
        pytest.skip("compiled (non-interpret) Pallas kernels need a TPU backend")


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (b, sq, skv, nq, nkv, hd, causal, window, blk)
    (2, 128, 128, 4, 4, 64, True, 0, 64),
    (1, 256, 256, 8, 2, 32, True, 0, 128),
    (2, 128, 128, 4, 1, 64, True, 64, 64),   # MQA + sliding window
    (1, 96, 96, 4, 4, 64, False, 0, 64),     # encoder (non-divisible seq)
    (1, 200, 200, 2, 2, 128, True, 0, 128),  # padded seq
]


@pytest.mark.parametrize("case", FLASH_CASES, ids=[str(c) for c in FLASH_CASES])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(case, dtype):
    b, sq, skv, nq, nkv, hd, causal, window, blk = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, sq, nq, hd), dtype)
    k = jax.random.normal(ks[1], (b, skv, nkv, hd), dtype)
    v = jax.random.normal(ks[2], (b, skv, nkv, hd), dtype)
    out = flash_attention_op(q, k, v, causal=causal, window=window,
                             blk_q=blk, blk_k=blk, interpret=True)

    def pack(x, h):
        return jnp.moveaxis(x, 2, 1).reshape(b * h, x.shape[1], hd)

    ref = flash_attention_ref(pack(q, nq), pack(k, nkv), pack(v, nkv),
                              n_q_heads=nq, n_kv_heads=nkv, causal=causal, window=window)
    ref = jnp.moveaxis(ref.reshape(b, nq, sq, hd), 1, 2)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol(dtype))


def test_flash_matches_model_sdpa():
    from repro.models.attention import sdpa

    q = jax.random.normal(KEY, (2, 64, 8, 32))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (2, 64, 2, 32))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (2, 64, 2, 32))
    out = flash_attention_op(q, k, v, causal=True, blk_q=32, blk_k=32, interpret=True)
    ref = sdpa(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# rg_lru
# ---------------------------------------------------------------------------

RG_CASES = [
    (1, 64, 32, 32, 32),
    (2, 128, 256, 64, 128),
    (3, 100, 48, 32, 16),  # non-divisible seq and d
]


@pytest.mark.parametrize("case", RG_CASES, ids=[str(c) for c in RG_CASES])
@pytest.mark.parametrize("with_h0", [False, True])
def test_rg_lru(case, with_h0):
    b, s, d, blk_s, blk_d = case
    ks = jax.random.split(KEY, 3)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (b, s, d))) * 0.98
    bb = jax.random.normal(ks[1], (b, s, d)) * 0.1
    h0 = jax.random.normal(ks[2], (b, d)) if with_h0 else None
    out = rg_lru_op(a, bb, h0, blk_s=blk_s, blk_d=blk_d, interpret=True)
    ref = rg_lru_ref(a, bb, h0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_rg_lru_matches_model_scan():
    """Kernel == the model's associative-scan training path."""
    from repro.configs import get_smoke
    from repro.models import rglru as RG

    cfg = get_smoke("recurrentgemma_9b")
    p = RG.init_rglru(KEY, cfg, jnp.float32)
    u = jax.random.normal(jax.random.fold_in(KEY, 7), (2, 32, cfg.resolved_d_rnn))
    a, b = RG._gates(p, u)
    href, _ = RG.rglru_scan(p, u)
    hker = rg_lru_op(a, b, blk_s=16, blk_d=32, interpret=True)
    np.testing.assert_allclose(np.asarray(hker), np.asarray(href, np.float32), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# lstm_cell
# ---------------------------------------------------------------------------

LSTM_CASES = [
    (4, 16, 32, 4, 16),
    (8, 64, 64, 8, 32),
    (5, 24, 48, 8, 48),  # non-divisible batch
]


@pytest.mark.parametrize("case", LSTM_CASES, ids=[str(c) for c in LSTM_CASES])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lstm_cell(case, dtype):
    b, d_in, hidden, blk_b, blk_h = case
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (b, d_in), dtype)
    h = jax.random.normal(ks[1], (b, hidden), dtype)
    c = jax.random.normal(ks[2], (b, hidden), dtype)
    params = {
        "wx": jax.random.normal(ks[3], (d_in, 4 * hidden), dtype) * 0.1,
        "wh": jax.random.normal(ks[4], (hidden, 4 * hidden), dtype) * 0.1,
        "b": jax.random.normal(ks[5], (4 * hidden,), dtype) * 0.1,
    }
    ho, co = lstm_cell_op(x, h, c, params, blk_b=blk_b, blk_h=blk_h, interpret=True)
    hr, cr = lstm_cell_ref(x, h, c,
                           params["wx"].reshape(d_in, 4, hidden),
                           params["wh"].reshape(hidden, 4, hidden),
                           params["b"].reshape(4, hidden))
    np.testing.assert_allclose(np.asarray(ho, np.float32), np.asarray(hr, np.float32), **tol(dtype))
    np.testing.assert_allclose(np.asarray(co, np.float32), np.asarray(cr, np.float32), **tol(dtype))


def test_lstm_cell_matches_model_cell():
    from repro.models.seq2seq import LSTMState, init_lstm_layer, lstm_cell as model_cell

    p = init_lstm_layer(KEY, 16, 32, 0.1, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (4, 16))
    h = jax.random.normal(jax.random.fold_in(KEY, 2), (4, 32))
    c = jax.random.normal(jax.random.fold_in(KEY, 3), (4, 32))
    ho, co = lstm_cell_op(x, h, c, p, blk_b=4, blk_h=32, interpret=True)
    st = model_cell(p, x, LSTMState(h, c))
    np.testing.assert_allclose(np.asarray(ho), np.asarray(st.h), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(co), np.asarray(st.c), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# text_clean
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("blk", [4, 64])
def test_text_clean_vs_ref(blk):
    rows = [
        "Hello <b>World</b> 42!",
        "plain text only",
        "UPPER and (kept by kernel) 123",
        "",
    ] * 7
    mat = pack_rows(rows)
    out = text_clean_op(mat, blk_rows=blk, interpret=True)
    ref = text_clean_ref(mat)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("blk", [64])
def test_text_clean_compiled_on_tpu(tpu, blk):
    """Same parity as above but Mosaic-compiled — TPU capability gated."""
    rows = ["Hello <b>World</b> 42!", "plain text only", ""] * 11
    mat = pack_rows(rows, width=128)
    out = text_clean_op(mat, blk_rows=blk, interpret=False)
    ref = text_clean_ref(mat)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_flash_attention_compiled_on_tpu(tpu):
    b, s, h, hd = 1, 128, 4, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, hd))
    k = jax.random.normal(ks[1], (b, s, h, hd))
    v = jax.random.normal(ks[2], (b, s, h, hd))
    out = flash_attention_op(q, k, v, causal=True, blk_q=64, blk_k=64, interpret=False)
    ref = flash_attention_op(q, k, v, causal=True, blk_q=64, blk_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# text_scan (the bytesops "pallas" backend kernel)
# ---------------------------------------------------------------------------

SCAN_ROWS = [
    "Hello <b>World</b> 42!",
    "plain text only",
    "(paren) and <tag> together",
    "<a(b>c)d adversarial nesting",
    "(a(b<c)d>e stray ) closer",
    "unclosed <span swallows",
    ">> leading closers ((",
    "",
] * 3


@pytest.mark.parametrize("flags", [
    dict(lower=True, strip_html=True, strip_parens=True),
    dict(lower=True, strip_html=True, strip_parens=False),
    dict(lower=False, strip_html=False, strip_parens=True),
    dict(lower=True, strip_html=False, strip_parens=False),
])
def test_text_scan_vs_ref(flags):
    from repro.kernels.text_clean.ops import text_scan_op
    from repro.kernels.text_clean.ref import text_scan_ref

    mat = pack_rows(SCAN_ROWS)
    out = text_scan_op(mat, blk_rows=8, interpret=True, **flags)
    ref = text_scan_ref(mat, **flags)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_text_scan_compiled_on_tpu(tpu):
    from repro.kernels.text_clean.ops import text_scan_op
    from repro.kernels.text_clean.ref import text_scan_ref

    mat = pack_rows(SCAN_ROWS, width=128)
    out = text_scan_op(mat, lower=True, strip_html=True, strip_parens=True,
                       interpret=False)
    ref = text_scan_ref(mat, lower=True, strip_html=True, strip_parens=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_scan_flat_matches_loops_ops(monkeypatch):
    """The flat-buffer bridge (pad → kernel → compact) must be
    byte-identical to the sequential loops ops it replaces — including
    non-ASCII bytes and the adversarial nesting rows."""
    from repro.core import bytesops as B
    from repro.kernels.text_clean.ops import scan_flat

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    rows = SCAN_ROWS + ["naïve café 漢字 🙂 (ñé) <Ω>", "tab\there"]
    buf = B.flatten(rows)
    ops = [B.lut_op(B.LOWER_LUT), B.span_op("<", ">"), B.span_op("(", ")")]
    want = B.apply_ops(buf, ops)
    got = scan_flat(buf, lower=True, strip_html=True, strip_parens=True)
    assert got is not None, "bridge declined despite REPRO_PALLAS_INTERPRET"
    np.testing.assert_array_equal(got, want)


def test_scan_flat_declines_safely(monkeypatch):
    """Without a TPU or the interpret override the bridge must decline
    (return None) rather than run the interpreter in production."""
    from repro.core import bytesops as B
    from repro.kernels.pallas_compat import has_tpu
    from repro.kernels.text_clean.ops import scan_flat

    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    buf = B.flatten(["some text <b>here</b>"])
    out = scan_flat(buf, lower=True, strip_html=True)
    if not has_tpu():
        assert out is None


def test_text_clean_matches_host_stages():
    """Device kernel == host ConvertToLower+RemoveHTMLTags+char-class LUT."""
    from repro.core import bytesops as B

    rows = ["Hello <i>World</i>, 42 Things!", "MiXeD CaSe <p>tag</p> end"]
    out = clean_rows(rows, interpret=True)
    expect = []
    for r in rows:
        buf = B.flatten([r])
        buf = B.apply_lut(buf, B.LOWER_LUT)
        buf = B.span_strip(buf, ord("<"), ord(">"))
        buf = B.apply_lut(buf, B.UNWANTED_LUT)
        buf = B.collapse_spaces(buf)
        expect.append(B.unflatten(buf)[0])
    assert out == expect


def test_scan_flat_shapes_are_bounded():
    """``scan_flat`` pads onto a small shape ladder: lane-aligned widths
    under 2x, row counts under 2x in whole row blocks, and a handful of
    distinct shapes per width for every row count up to 20k."""
    from repro.kernels.text_clean.ops import padded_shape
    from repro.kernels.text_clean.text_clean import LANE_TILE, ROW_TILE, block_rows

    rows_per_width: dict[int, set] = {}
    for n in range(1, 20001, 3):
        for w in (1, 100, 129, 1738, 5000):
            r, wp = padded_shape(n, w)
            assert wp >= w and wp % LANE_TILE == 0 and wp < 2 * max(w, LANE_TILE)
            assert n <= r <= 2 * max(n, ROW_TILE) and r % ROW_TILE == 0
            assert r % min(block_rows(wp), r) == 0
            rows_per_width.setdefault(w, set()).add(r)
    assert max(len(rs) for rs in rows_per_width.values()) <= 20


@pytest.mark.parametrize("interpret_env", [True, False])
def test_pallas_backend_counts_kernel_calls(monkeypatch, interpret_env):
    """``execute_ops(..., "pallas", stats=...)`` counts each scan pass the
    kernel ran and each the bridge declined; the bytes equal the loops
    backend's either way. Off-TPU the bridge declines unless interpret
    mode is forced."""
    from repro.core import bytesops as B
    from repro.core import expr as E

    if interpret_env:
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    ops = list(E.compile_expr(E.abstract_expr())[2])
    buf = B.flatten(SCAN_ROWS + ["Don't <i>STOP</i> (ever) now"])
    stats: dict = {}
    got = B.execute_ops(buf, ops, "pallas", stats=stats)
    np.testing.assert_array_equal(got, B.execute_ops(buf, ops, "loops"))
    if interpret_env:
        assert stats.get("pallas_calls", 0) >= 1 and "pallas_declines" not in stats
    else:
        assert stats.get("pallas_declines", 0) >= 1 and "pallas_calls" not in stats
