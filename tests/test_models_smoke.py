"""Per-architecture smoke tests (reduced same-family configs, CPU):
one forward + one train step, asserting output shapes and finiteness, plus
decode==forward consistency and published-size parameter counts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, all_cells, get, get_smoke
from repro.models.lm import LM

KEY = jax.random.PRNGKey(0)


def make_batch(cfg, b=2, s=16):
    batch = {"tokens": jax.random.randint(KEY, (b, s), 4, cfg.vocab_size)}
    if cfg.frontend == "audio":
        batch = {
            "frames": jax.random.normal(KEY, (b, s, cfg.frontend_dim)),
            "labels": jax.random.randint(KEY, (b, s), 0, cfg.vocab_size),
        }
    elif cfg.frontend == "vision":
        n_img = min(cfg.n_frontend_tokens, s)
        batch["patches"] = jax.random.normal(KEY, (b, n_img, cfg.frontend_dim))
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finite(arch):
    cfg = get_smoke(arch)
    model = LM(cfg, remat=False, dtype=jnp.float32)
    params = model.init(KEY)
    b, s = 2, 16
    batch = make_batch(cfg, b, s)
    logits, aux = jax.jit(model.forward)(params, batch)
    assert logits.shape == (b, s, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_one_train_step(arch):
    cfg = get_smoke(arch)
    model = LM(cfg, remat=True, dtype=jnp.float32)
    params = model.init(KEY)
    batch = make_batch(cfg)

    @jax.jit
    def step(p, b):
        loss, grads = jax.value_and_grad(model.loss)(p, b)
        p = jax.tree.map(lambda w, g: w - 1e-2 * g, p, grads)
        return p, loss

    p1, l1 = step(params, batch)
    p2, l2 = step(p1, batch)
    assert np.isfinite(float(l1)) and np.isfinite(float(l2))
    assert float(l2) < float(l1)  # same-batch loss must drop


@pytest.mark.parametrize("arch", [a for a in ARCH_IDS if get_smoke(a).causal])
def test_decode_matches_forward(arch):
    cfg = get_smoke(arch)
    model = LM(cfg, remat=False, dtype=jnp.float32)
    params = model.init(KEY)
    b, s = 2, 10
    toks = jax.random.randint(KEY, (b, s), 4, cfg.vocab_size)
    full_logits, _ = model.forward(params, {"tokens": toks})
    state = model.init_decode_state(b, 16, cache_dtype=jnp.float32)
    step = jax.jit(model.decode_step)
    for t in range(s):
        lg, state = step(params, toks[:, t : t + 1], state, jnp.int32(t))
        np.testing.assert_allclose(
            np.asarray(lg[:, 0]), np.asarray(full_logits[:, t]), rtol=1e-4, atol=1e-4
        )


# Published sizes (billions), tolerance generous (embedding conventions vary)
_EXPECTED_B = {
    "hubert_xlarge": (0.9, 1.1),
    "deepseek_moe_16b": (15.0, 18.0),
    "kimi_k2_1t_a32b": (950.0, 1100.0),
    "stablelm_3b": (2.5, 3.2),
    "command_r_plus_104b": (95.0, 110.0),
    "granite_20b": (18.0, 22.0),
    "qwen2_5_32b": (30.0, 35.0),
    "recurrentgemma_9b": (8.5, 10.5),
    "xlstm_1_3b": (1.1, 1.7),
    "qwen2_vl_72b": (68.0, 76.0),
    "deepseek_v2_lite": (15.0, 16.5),
}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_count_matches_published(arch):
    lo, hi = _EXPECTED_B[arch]
    n = get(arch).param_count() / 1e9
    assert lo <= n <= hi, f"{arch}: {n:.2f}B not in [{lo},{hi}]"


def test_kimi_active_params():
    cfg = get("kimi_k2_1t_a32b")
    active = cfg.active_param_count() / 1e9
    assert 28.0 <= active <= 40.0  # a32b


def test_deepseek_v2_lite_active_params():
    active = get("deepseek_v2_lite").active_param_count() / 1e9
    assert 2.2 <= active <= 2.8  # published 15.7B-A2.4B; embedding and head counted here


def test_cell_grid():
    cells = all_cells()
    assert len(cells) == 34
    assert ("hubert_xlarge", "decode_32k") not in cells
    assert ("hubert_xlarge", "long_500k") not in cells
    assert ("recurrentgemma_9b", "long_500k") in cells
    assert ("xlstm_1_3b", "long_500k") in cells
    assert ("qwen2_5_32b", "long_500k") not in cells


def test_moe_local_matches_manual():
    """Routed-expert output == manual per-token dense computation."""
    from repro.models import moe as MOE

    cfg = get_smoke("deepseek_moe_16b")
    p = MOE.init_moe(KEY, cfg, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(KEY, 5), (2, 8, cfg.d_model)) * 0.5
    y, aux = MOE.moe_local(p, x, cfg)
    m = cfg.moe
    xf = x.reshape(-1, cfg.d_model)
    ids, probs, _ = MOE._route(xf, p["router"], m)
    expect = np.zeros_like(np.asarray(xf))
    for t in range(xf.shape[0]):
        for j in range(m.top_k):
            e = int(ids[t, j])
            h = jax.nn.silu(xf[t] @ p["w_gate"][e]) * (xf[t] @ p["w_up"][e])
            expect[t] += float(probs[t, j]) * np.asarray(h @ p["w_down"][e])
    np.testing.assert_allclose(np.asarray(y.reshape(-1, cfg.d_model)), expect, rtol=2e-4, atol=2e-4)
