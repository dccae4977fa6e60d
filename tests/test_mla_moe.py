"""Latent attention, held expert shares and YaRN (DeepSeek-V2-Lite), on the
CPU at a small size with seeded float32 weights, against the plain
reference ``bench/reference/mla_moe.py`` and against each other."""

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.reference import mla_moe as ref  # noqa: E402
from bench.tests import mla_smoke  # noqa: E402
from repro.configs import get, get_smoke  # noqa: E402
from repro.models import attention as A  # noqa: E402
from repro.models import moe as MOE  # noqa: E402
from repro.models.blocks import rope_frequencies, yarn_correction_range  # noqa: E402
from repro.models.lm import LM  # noqa: E402

KEY = jax.random.PRNGKey(0)

# float32 on both sides, the same equations in another order (absorbed
# against expanded attention, a cache against none): the gaps are float32
# round-off, ~1e-6 on logits of magnitude ~3; the float8 control lies ~0.2 off.
LOGIT_ATOL = 2e-5


def _model_and_params(**moe):
    cfg = mla_smoke.cfg(dtype="float32")
    params = ref.init_params(KEY, cfg)
    return LM(mla_smoke.arch(**moe), remat=False, dtype=jnp.float32), params, cfg


def test_prefill_then_latent_decode_matches_the_reference_forward():
    model, params, cfg = _model_and_params()
    b, prompt, total = 2, 12, 24
    toks = np.asarray(jax.random.randint(jax.random.fold_in(KEY, 1), (b, total), 4, cfg["vocab_size"]))
    state = model.init_decode_state(b, 32, cache_dtype=jnp.float32)
    assert isinstance(state["head"][0], A.MLACache)
    step = jax.jit(model.decode_step)
    with MOE.tally_paths() as prefill_took:
        logits, state = step(params, jnp.asarray(toks[:, :prompt]), state, jnp.int32(0))
    got = [logits[:, 0]]
    with MOE.tally_paths() as decode_took:
        for t in range(prompt, total):
            logits, state = step(params, jnp.asarray(toks[:, t : t + 1]), state, jnp.int32(t))
            got.append(logits[:, 0])
    # b = 2 decode tokens <= 2 held experts: the reached path; the prompt's 24 tokens: ragged
    assert set(prefill_took) == {"ragged"} and set(decode_took) == {"reached"}
    got = np.stack(got, 1)
    rows = np.repeat(np.arange(b), total - prompt + 1).astype(np.int32)
    cols = np.tile(np.arange(prompt - 1, total), b).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = ref._logits_at(params, jnp.asarray(toks), rows, cols, m=ref.model(cfg), quant=False)
        low = ref._logits_at(params, jnp.asarray(toks), rows, cols, m=ref.model(cfg), quant=True)
    want, low = np.asarray(want).reshape(got.shape), np.asarray(low).reshape(got.shape)
    np.testing.assert_allclose(got, want, rtol=0, atol=LOGIT_ATOL)
    assert np.abs(low - want).max() > 100 * LOGIT_ATOL  # the tolerance would catch float8


def test_held_shares_sum_to_the_uncut_layer():
    """8 experts in 4 shares of 2: each share routes over all 8 and computes
    its own experts' part; the parts, with the shared experts once, add up
    to the uncut reference layer."""
    cfg = mla_smoke.cfg(dtype="float32", n_routed_experts=8)
    uncut = ref.init_params(KEY, cfg)["units"][0]["moe"]
    p = jax.tree.map(lambda a: a[1], uncut)  # MoE layer 2 of the stack
    arch = mla_smoke.arch()
    # 16 tokens take the grouped path in every share; 1 token the reached path
    for shape in ((2, 8, cfg["hidden_size"]), (1, 1, cfg["hidden_size"])):
        x = jax.random.normal(jax.random.fold_in(KEY, 2), shape)
        parts = []
        for first in range(0, 8, 2):
            share_cfg = dataclasses.replace(arch, moe=dataclasses.replace(arch.moe, held=2, first_held=first))
            share = dict(p, **{k: p[k][first : first + 2] for k in MOE.EXPERT_KEYS})
            routed, _ = MOE.moe_local(share, x, share_cfg)
            layer, _ = MOE.apply_moe(share, x, share_cfg)  # what the chip holding this share adds
            parts.append((routed, layer))
        shared = parts[0][1] - parts[0][0]  # computed alike on every chip: counted once
        total = sum(routed for routed, _ in parts) + shared
        with jax.default_matmul_precision("highest"):
            want = ref._experts(x, p, ref.model(cfg), False)
        assert float(jnp.abs(shared).max()) > 0
        np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=0, atol=1e-5)
        uncut_cfg = dataclasses.replace(arch, moe=dataclasses.replace(arch.moe, held=8, first_held=0))
        whole, _ = MOE.apply_moe(p, x, uncut_cfg)
        np.testing.assert_allclose(np.asarray(whole), np.asarray(want), rtol=0, atol=1e-5)


def test_top_k_renormalisation_follows_the_config():
    arch = get_smoke("deepseek_moe_16b")
    p = MOE.init_moe(KEY, arch, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(KEY, 3), (2, 8, arch.d_model))
    xf = x.reshape(-1, arch.d_model)
    full = jax.nn.softmax(xf @ p["router"], axis=-1)
    top, _ = jax.lax.top_k(full, arch.moe.top_k)
    _, probs, _ = MOE._route(xf, p["router"], arch.moe)
    np.testing.assert_allclose(np.asarray(probs), np.asarray(top / top.sum(-1, keepdims=True)), rtol=1e-6)
    raw = dataclasses.replace(arch.moe, norm_topk_prob=False)
    _, unnormalised, _ = MOE._route(xf, p["router"], raw)
    np.testing.assert_allclose(np.asarray(unnormalised), np.asarray(top), rtol=1e-6)
    assert float(unnormalised.sum(-1).max()) < 1.0
    _, scaled, _ = MOE._route(xf, p["router"], dataclasses.replace(raw, routed_scaling_factor=2.5))
    np.testing.assert_allclose(np.asarray(scaled), 2.5 * np.asarray(top), rtol=1e-6)
    # deepseek_moe_16b as before: every assignment held, weights renormalised
    y, _ = MOE.moe_local(p, x, arch)
    ids = jax.lax.top_k(full, arch.moe.top_k)[1]
    w = top / top.sum(-1, keepdims=True)
    expect = np.zeros(xf.shape, np.float32)
    for t in range(xf.shape[0]):
        for j in range(arch.moe.top_k):
            e = int(ids[t, j])
            h = jax.nn.silu(xf[t] @ p["w_gate"][e]) * (xf[t] @ p["w_up"][e])
            expect[t] += float(w[t, j]) * np.asarray(h @ p["w_down"][e])
    np.testing.assert_allclose(np.asarray(y.reshape(xf.shape)), expect, rtol=2e-4, atol=2e-4)


def _published_rope_scaling() -> dict:
    return json.loads((ROOT / "bench" / "configs" / "deepseek-v2-lite.json").read_text())["rope_scaling"]


def test_yarn_at_the_published_values():
    arch = get("deepseek_v2_lite")
    assert yarn_correction_range(arch.rope_scaling, 64, arch.rope_theta) == (10, 23)
    assert A.mla_softmax_scale(arch) == pytest.approx(0.114721, abs=1e-6)
    cfg = mla_smoke.cfg(rope_scaling=_published_rope_scaling(), qk_rope_head_dim=64, qk_nope_head_dim=128)
    inv_freq, factor, scale, edges = ref.yarn(cfg)
    assert edges == (10, 23) and factor == 1.0
    assert scale == pytest.approx(A.mla_softmax_scale(arch), rel=1e-12)
    np.testing.assert_allclose(rope_frequencies(64, arch.rope_theta, arch.rope_scaling), inv_freq, rtol=1e-12)
    plain = rope_frequencies(64, arch.rope_theta)
    np.testing.assert_allclose(inv_freq[:10], plain[:10])  # below low: unscaled
    np.testing.assert_allclose(inv_freq[24:], plain[24:] / 40)  # above high: interpolated


@pytest.mark.parametrize(
    "block, scope", [(5, "mla.prefill"), (1, "mla.decode"), (1, "moe.experts.reached")]
)
def test_named_scopes_in_the_lowered_programs(block, scope):
    model, params, _ = _model_and_params()
    state = model.init_decode_state(1, 16, cache_dtype=jnp.float32)
    toks = jnp.ones((1, block), jnp.int32)
    text = jax.jit(model.decode_step).lower(params, toks, state, jnp.int32(0)).as_text(debug_info=True)
    for name in (scope, "moe.route", "moe.experts", "moe.shared"):
        assert name in text, name
    other = "mla.decode" if scope == "mla.prefill" else "mla.prefill"
    assert other not in text
    # the reached experts' scope marks decode (block <= 2 held), and only decode
    assert ("moe.experts.reached" in text) == (block <= model.cfg.moe.n_held)


def test_state_bytes_counts_one_latent_slot():
    from repro.runtime.serve_loop import ServeStats, _continuous_decode

    arch = get("deepseek_v2_lite_ep8")
    stats = ServeStats()
    _continuous_decode(LM(arch, dtype=jnp.bfloat16), None, lambda: None, lambda *a: None,
                       slots=4, max_seq=256, cache_dtype=jnp.bfloat16, stats=stats)
    assert stats.state_bytes == 27 * 256 * (512 + 64) * 2


def _share(first_held: int, dtype):
    """8 routed experts, top-3, a layer holding 4 of them from ``first_held``,
    with weights of unit scale so the outputs are of order 1."""
    arch = mla_smoke.arch(held=4, first_held=first_held, top_k=3)
    d, f = arch.d_model, arch.moe.d_expert
    kg, ku, kd = jax.random.split(jax.random.fold_in(KEY, 6), 3)
    p = {
        "w_gate": (jax.random.normal(kg, (4, d, f)) / np.sqrt(d)).astype(dtype),
        "w_up": (jax.random.normal(ku, (4, d, f)) / np.sqrt(d)).astype(dtype),
        "w_down": (jax.random.normal(kd, (4, f, d)) / np.sqrt(f)).astype(dtype),
    }
    return arch, p


def _assignments(m, n: int, held: int, seed: int):
    """Each of n tokens routed to top_k distinct experts, ``held`` of them in
    the layer's share, in a shuffled top-k order; probs of each token's k."""
    rng = np.random.default_rng(seed)
    inside = np.arange(m.first_held, m.first_held + m.n_held)
    outside = np.setdiff1d(np.arange(m.n_experts), inside)
    ids = np.stack([
        rng.permutation(np.concatenate([rng.choice(inside, held, replace=False),
                                        rng.choice(outside, m.top_k - held, replace=False)]))
        for _ in range(n)
    ])
    return jnp.asarray(ids, jnp.int32), jnp.asarray(rng.dirichlet(np.ones(m.top_k), n))


# float32: the same products summed in another order (ragged_dot's lowering
# against plain dots); bfloat16: the tolerance of the bfloat16 kernel parity
# tests, ~2 ulps of an output of order 1 (each GLU and product is rounded to
# bfloat16 at the same points on both paths).
REACHED_TOL = {jnp.float32: dict(rtol=1e-5, atol=1e-6), jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("first_held", [0, 4])
@pytest.mark.parametrize("n", [1, 4], ids=["n1", "n_held"])
@pytest.mark.parametrize("held", [0, 1, 3], ids=["none_held", "one_held", "all_held"])
def test_reached_experts_equal_the_ragged_path(dtype, first_held, n, held):
    """The reached path gives what the grouped (ragged) path gives on the
    same assignments, and a layer scan's whole stacks indexed by layer give
    what that layer's slice gives."""
    arch, p = _share(first_held, dtype)
    m = arch.moe
    x = jax.random.normal(jax.random.fold_in(KEY, 7 + n), (n, arch.d_model)).astype(dtype)
    ids, probs = _assignments(m, n, held, seed=100 * n + 10 * held + first_held)
    probs = probs.astype(dtype)
    got = MOE._reached_experts(x, ids, probs, p, m)
    want = MOE._grouped_experts(x, ids, probs, p, m)
    assert got.dtype == want.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               **REACHED_TOL[dtype])
    if held == 0:
        assert not np.asarray(got, np.float32).any()
    else:
        assert np.abs(np.asarray(want, np.float32)).max() > 0.1
    stacks = {k: jnp.stack([-p[k], p[k], 2 * p[k]]) for k in MOE.EXPERT_KEYS}
    at_layer = MOE._reached_experts(x, ids, probs, stacks, m, layer=jnp.int32(1))
    np.testing.assert_array_equal(np.asarray(at_layer, np.float32), np.asarray(got, np.float32))


@pytest.mark.parametrize("n, path", [(1, "reached"), (8, "reached"), (9, "ragged"), (64, "ragged")])
def test_the_token_count_chooses_the_expert_path(n, path):
    """At most n_held tokens (a decode step of up to 8 slots) read the
    reached experts; a prefill-sized count keeps the grouped GEMM."""
    arch = get("deepseek_v2_lite_ep8")
    m = arch.moe
    d, f = arch.d_model, m.d_expert
    p = {
        "router": jax.ShapeDtypeStruct((d, m.n_experts), jnp.float32),
        "w_gate": jax.ShapeDtypeStruct((m.n_held, d, f), jnp.bfloat16),
        "w_up": jax.ShapeDtypeStruct((m.n_held, d, f), jnp.bfloat16),
        "w_down": jax.ShapeDtypeStruct((m.n_held, f, d), jnp.bfloat16),
    }
    x = jax.ShapeDtypeStruct((1, n, d), jnp.bfloat16)
    with MOE.tally_paths() as took:
        text = jax.jit(lambda p, x: MOE.moe_local(p, x, arch)).lower(p, x).as_text(debug_info=True)
    assert dict(took) == {path: 1}
    assert ("ragged_dot" in text) == (path == "ragged")
    assert ("moe.experts.reached" in text) == (path == "reached")


def test_serve_stats_name_the_expert_path_of_each_program():
    """A served call's stats say that its step program took the reached
    path and its prefill the ragged one; a model without MoE names none."""
    from repro.runtime.serve_loop import ServeStats, _continuous_decode

    model, params, _ = _model_and_params()
    prompts = [np.arange(4, 9, dtype=np.int32), np.arange(10, 16, dtype=np.int32)]
    queue = list(enumerate(prompts))
    done = {}
    stats = ServeStats()
    _continuous_decode(model, params, lambda: (*queue.pop(), 3) if queue else None, done.__setitem__,
                       slots=2, max_seq=16, stats=stats)
    assert sorted(done) == [0, 1] and all(len(out) == 3 for out in done.values())
    assert stats.moe_paths == {"step": ("reached",), "prefill": ("ragged",)}
    dense = LM(dataclasses.replace(get_smoke("stablelm_3b"), vocab_size=64), remat=False)
    dense_stats = ServeStats()
    queue = list(enumerate(prompts))
    _continuous_decode(dense, dense.init(KEY), lambda: (*queue.pop(), 2) if queue else None,
                       lambda *a: None, slots=2, max_seq=16, stats=dense_stats)
    assert dense_stats.moe_paths == {}


@pytest.mark.parametrize("sq, skv", [(7, 7), (3, 40)])
def test_sdpa_takes_the_output_width_from_v(sq, skv):
    kq, kk, kv = jax.random.split(jax.random.fold_in(KEY, 4), 3)
    q = jax.random.normal(kq, (2, sq, 4, 192))
    k = jax.random.normal(kk, (2, skv, 4, 192))
    v = jax.random.normal(kv, (2, skv, 4, 128))
    off = skv - sq
    scores = jnp.einsum("bshk,bthk->bhst", q, k) * 0.1
    mask = jnp.arange(skv)[None, :] <= jnp.arange(sq)[:, None] + off
    naive = jnp.einsum("bhst,bthk->bshk", jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1), v)
    kw = dict(causal=True, q_offset=off, scale=0.1)
    for out in (A.sdpa(q, k, v, **kw), A.chunked_sdpa(q, k, v, q_chunk=2, kv_chunk=16, **kw)):
        assert out.shape == (2, sq, 4, 128)
        np.testing.assert_allclose(np.asarray(out), np.asarray(naive), rtol=1e-5, atol=1e-5)
