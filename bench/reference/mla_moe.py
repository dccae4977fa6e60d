"""Plain reference of DeepSeek-V2-Lite as one chip of an expert-parallel
deployment holds it, and its float8 control.

The configuration's model as DeepSeek-V2 (arXiv 2405.04434) defines it,
computed in float32 at ``highest`` matmul precision from the served
bfloat16 weights, layer by layer over blocks of right-padded sequences,
with no cache and no absorption:

* the token embedding, unscaled;
* per layer, a pre-RMSNorm (eps ``rms_norm_eps``) latent attention without
  query compression: q = x W_q, each head ``qk_nope_head_dim`` unrotated
  plus ``qk_rope_head_dim`` rotary dims; [c_kv; k_pe] = x W_kv_a, c_kv
  through its own RMSNorm, k_pe one rotary key shared by all heads;
  [k_nope; v] = c_kv W_kv_b; causal softmax of (q_nope.k_nope +
  q_pe.k_pe) x softmax_scale over the earlier positions, then the heads'
  values through W_o;
* YaRN rotary (``rope_scaling``): frequencies blended between theta's and
  theta's over ``factor`` by a linear ramp over the pairs between the
  correction range's low and high, cos and sin times mscale(factor,
  mscale) / mscale(factor, mscale_all_dim), and softmax_scale =
  (qk head dim)^-1/2 x mscale(factor, mscale_all_dim)^2, with mscale(s,
  m) = 0.1 m ln s + 1;
* then a pre-RMSNorm SiLU GLU: dense of ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers; in the others a softmax router over all
  ``router_experts``, greedy top ``num_experts_per_tok``, renormalised
  only where ``norm_topk_prob``, times ``routed_scaling_factor``; each of
  the ``n_routed_experts`` held experts (from ``first_held_expert``)
  weighted by its routing weight (0 where the token did not choose it),
  plus ``n_shared_experts`` shared experts as one GLU. What the experts
  held on other chips would add is left out, as on the chip;
* a final RMSNorm and an untied output head.

Rotary dims are rotated as two halves; the published checkpoint stores
them as interleaved pairs, a fixed permutation of the same columns.

``quant=True`` is the control: every product the program computes in
bfloat16 at float8 e4m3 (weights per output channel, inputs per token),
the router's, which the program computes in float32, at bfloat16.

A serving configuration names this module with ``"reference": "mla_moe"``;
the serve driver takes from it ``PROGRAM_KEYS``, ``init_params``,
``request_flops`` and ``served_gaps``; ``step_bytes`` is read by the
``serve.step_hbm_share`` metric.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.lm import _linear

# configuration file key -> the attribute of the program's ArchConfig that
# must equal it (a dotted path reaches into a nested group)
PROGRAM_KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "vocab_size": "vocab_size",
    "intermediate_size": "moe.d_ff_dense",
    "moe_intermediate_size": "moe.d_expert",
    "router_experts": "moe.n_experts",
    "n_routed_experts": "moe.n_held",
    "first_held_expert": "moe.first_held",
    "num_experts_per_tok": "moe.top_k",
    "n_shared_experts": "moe.n_shared",
    "first_k_dense_replace": "moe.first_k_dense",
    "norm_topk_prob": "moe.norm_topk_prob",
    "routed_scaling_factor": "moe.routed_scaling_factor",
    "kv_lora_rank": "mla.kv_lora_rank",
    "qk_nope_head_dim": "mla.qk_nope_head_dim",
    "qk_rope_head_dim": "mla.qk_rope_head_dim",
    "v_head_dim": "mla.v_head_dim",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}

BLOCK = 8  # sequences a reference forward pass takes at once


class Model(NamedTuple):
    """The configuration's numbers the forward pass needs (hashable)."""

    nope: int
    rope: int
    rank: int
    eps: float
    top_k: int
    norm_topk: bool
    routed_scale: float
    first_held: int
    inv_freq: tuple
    rope_factor: float
    softmax_scale: float


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn(cfg: dict) -> tuple[np.ndarray, float, float, tuple[int, int]]:
    """(inverse frequencies, cos/sin factor, softmax scale, (low, high))."""
    dim, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    base = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    scale = (cfg["qk_nope_head_dim"] + dim) ** -0.5
    y = cfg.get("rope_scaling")
    if not y:
        return base, 1.0, scale, (0, 0)
    f = float(y["factor"])

    def turns(beta):  # the pair that turns beta times in the original context
        return dim * math.log(y["original_max_position_embeddings"] / (beta * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(turns(y["beta_fast"])), 0)
    high = min(math.ceil(turns(y["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low if high > low else 0.001), 0.0, 1.0)
    inv_freq = base / f * ramp + base * (1.0 - ramp)
    factor = _mscale(f, y["mscale"]) / _mscale(f, y["mscale_all_dim"])
    if y["mscale_all_dim"]:
        scale *= _mscale(f, y["mscale_all_dim"]) ** 2
    return inv_freq, factor, scale, (low, high)


def model(cfg: dict) -> Model:
    inv_freq, factor, scale, _ = yarn(cfg)
    return Model(
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"], rank=cfg["kv_lora_rank"],
        eps=float(cfg["rms_norm_eps"]), top_k=cfg["num_experts_per_tok"],
        norm_topk=bool(cfg["norm_topk_prob"]), routed_scale=float(cfg["routed_scaling_factor"]),
        first_held=cfg["first_held_expert"], inv_freq=tuple(float(v) for v in inv_freq),
        rope_factor=factor, softmax_scale=scale,
    )


def init_params(key, cfg: dict) -> dict:
    """Seeded weights in the system's parameter layout: ``head`` holds the
    leading dense layers, ``units`` the MoE layers stacked on a leading
    axis, made in the configuration's ``dtype`` (the router in float32, as
    the program keeps it). Each matrix is normal with std 1/sqrt(fan_in);
    the projections back into the residual are scaled further by
    1/sqrt(2 * layers); the embedding has std 1, so a token enters the
    residual at unit scale (there is no embedding scale)."""
    d, v, n = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    e, f, fs = cfg["n_routed_experts"], cfg["moe_intermediate_size"], cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
    dense, fd = cfg["first_k_dense_replace"], cfg["intermediate_size"]
    dtype = getattr(jnp, cfg["dtype"])
    ks = iter(jax.random.split(key, 32))
    res = 1.0 / np.sqrt(2 * n)

    def normal(shape, std, kind=dtype):
        return (std * jax.random.normal(next(ks), shape, jnp.float32)).astype(kind)

    def layer(lead: tuple, moe: bool) -> dict:
        p = {
            "norm1": {"scale": jnp.ones(lead + (d,), dtype)},
            "attn": {
                "wq": normal(lead + (d, h, nope + rope), d**-0.5),
                "wkv_a": normal(lead + (d, r + rope), d**-0.5),
                "kv_norm": {"scale": jnp.ones(lead + (r,), dtype)},
                "wkv_b": normal(lead + (r, h, nope + vd), r**-0.5),
                "wo": normal(lead + (h, vd, d), res * (h * vd) ** -0.5),
            },
            "norm2": {"scale": jnp.ones(lead + (d,), dtype)},
        }
        if moe:
            p["moe"] = {
                "router": normal(lead + (d, cfg["router_experts"]), d**-0.5, jnp.float32),
                "w_gate": normal(lead + (e, d, f), d**-0.5),
                "w_up": normal(lead + (e, d, f), d**-0.5),
                "w_down": normal(lead + (e, f, d), res * f**-0.5),
                "shared": {
                    "gate": normal(lead + (d, fs), d**-0.5),
                    "up": normal(lead + (d, fs), d**-0.5),
                    "down": normal(lead + (fs, d), res * fs**-0.5),
                },
            }
        else:
            p["mlp"] = {
                "gate": normal(lead + (d, fd), d**-0.5),
                "up": normal(lead + (d, fd), d**-0.5),
                "down": normal(lead + (fd, d), res * fd**-0.5),
            }
        return p

    return {
        "embed": {"embedding": normal((v, d), 1.0), "lm_head": normal((d, v), d**-0.5)},
        "head": [layer((), False) for _ in range(dense)],
        "units": [layer((n - dense,), True)],
        "tail": [],
        "final_norm": {"scale": jnp.ones((d,), dtype)},
    }


def _norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rotate(x, pos, m: Model):
    """Rotary embedding of x (b, s, heads, rope) at positions pos (b, s)."""
    ang = pos[:, :, None, None].astype(jnp.float32) * jnp.asarray(m.inv_freq, jnp.float32)
    cos, sin = jnp.cos(ang) * m.rope_factor, jnp.sin(ang) * m.rope_factor
    x1, x2 = x[..., : m.rope // 2], x[..., m.rope // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _lin(x, w, quant):
    """x (..., in) @ w (in, ...), at float8 e4m3 in the control."""
    x, w = _linear(x, w, quant, 0)
    return jnp.tensordot(x, w, axes=1)


def _attention(x, a, m: Model, quant):
    b, s, _ = x.shape
    q = _lin(x, a["wq"], quant)  # (b, s, h, nope + rope)
    kv = _lin(x, a["wkv_a"], quant)  # (b, s, rank + rope)
    ckv = _norm(kv[..., : m.rank], a["kv_norm"]["scale"], m.eps)
    kvb = _lin(ckv, a["wkv_b"], quant)  # (b, s, h, nope + v)
    k_nope, v = kvb[..., : m.nope], kvb[..., m.nope :]
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    q_pe = _rotate(q[..., m.nope :], pos, m)
    k_pe = _rotate(kv[..., None, m.rank :], pos, m)
    scores = jnp.einsum("bshk,bthk->bhst", q[..., : m.nope], k_nope)
    scores += jnp.einsum("bshr,btr->bhst", q_pe, k_pe[:, :, 0])
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores * m.softmax_scale, -jnp.inf), -1)
    o = jnp.einsum("bhst,bthk->bshk", probs, v).reshape(b, s, -1)
    return _lin(o, a["wo"].reshape(-1, a["wo"].shape[-1]), quant)


def _glu(x, gate, up, down, quant):
    inner = jax.nn.silu(_lin(x, gate, quant)) * _lin(x, up, quant)
    return _lin(inner, down, quant)


def _experts(x, p, m: Model, quant):
    """The held routed experts' part of the output, and the shared experts'."""
    router_in, router = x, p["router"]
    if quant:
        router_in, router = router_in.astype(jnp.bfloat16), router.astype(jnp.bfloat16)
    logits = jnp.einsum("bsd,de->bse", router_in, router, preferred_element_type=jnp.float32)
    weight, chosen = jax.lax.top_k(jax.nn.softmax(logits, -1), m.top_k)
    if m.norm_topk:
        weight = weight / weight.sum(-1, keepdims=True)
    weight = weight * m.routed_scale
    held = m.first_held + jnp.arange(p["w_gate"].shape[0])
    gates = jnp.einsum("bsk,bske->bse", weight, (chosen[..., None] == held).astype(jnp.float32))
    xq, wg = _linear(x, p["w_gate"], quant, 1)
    _, wu = _linear(x, p["w_up"], quant, 1)
    inner = jax.nn.silu(jnp.einsum("bsd,edf->bsef", xq, wg)) * jnp.einsum("bsd,edf->bsef", xq, wu)
    inner, wd = _linear(inner, p["w_down"], quant, 1)
    routed = jnp.einsum("bse,bsef,efd->bsd", gates, inner, wd)
    sh = p["shared"]
    return routed + _glu(x, sh["gate"], sh["up"], sh["down"], quant)


def _layer(x, p, m: Model, *, quant):
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    x = x + _attention(_norm(x, p["norm1"]["scale"], m.eps), p["attn"], m, quant)
    h = _norm(x, p["norm2"]["scale"], m.eps)
    if "moe" in p:
        return x + _experts(h, p["moe"], m, quant)
    return x + _glu(h, p["mlp"]["gate"], p["mlp"]["up"], p["mlp"]["down"], quant)


@partial(jax.jit, static_argnames=("m", "quant"))
def _logits_at(params, tokens, rows, cols, *, m: Model, quant):
    """Logits (float32) at positions (rows[i], cols[i]) of ``tokens``."""
    x = params["embed"]["embedding"][tokens].astype(jnp.float32)
    for layer in params["head"]:
        x = _layer(x, layer, m, quant=quant)

    def body(x, layer):
        return _layer(x, layer, m, quant=quant), None

    x, _ = jax.lax.scan(body, x, params["units"][0])
    h = _norm(x[rows, cols], params["final_norm"]["scale"].astype(jnp.float32), m.eps)
    return _lin(h, params["embed"]["lm_head"].astype(jnp.float32), quant)


def served_gaps(params, prompts, served, cfg: dict, *, control: bool = False):
    """For each served token, how far the reference's logit of it lies
    below the reference's best logit at that position.

    ``prompts[i]`` and ``served[i]`` are the token ids of request i. The
    token ``served[i][j]`` was produced at position ``len(prompts[i]) - 1 + j``
    of the sequence ``prompts[i] + served[i][:-1]``. Returns the gaps as
    one float64 array; with ``control`` also the gaps of the tokens that
    the float8 control puts first at the same positions."""
    m = model(cfg)
    order = sorted(range(len(prompts)), key=lambda i: len(prompts[i]) + len(served[i]))
    gaps, ctrl_gaps = [], []
    # every served sequence fits the cache's max_seq positions: one block
    # shape, so the 27-layer reference compiles once (twice with control)
    width = cfg["serve"]["max_seq"]
    with jax.default_matmul_precision("highest"):
        for start in range(0, len(order), BLOCK):
            idx = order[start : start + BLOCK]
            seqs = [list(prompts[i]) + list(served[i][:-1]) for i in idx]
            tokens = np.zeros((BLOCK, max(width, max(len(s) for s in seqs))), np.int32)
            rows, cols, want = [], [], []
            for r, i in enumerate(idx):
                tokens[r, : len(seqs[r])] = seqs[r]
                for j, t in enumerate(served[i]):
                    rows.append(r)
                    cols.append(len(prompts[i]) - 1 + j)
                    want.append(t)
            n = len(want)
            pad = -(-n // 256) * 256 - n  # few position counts, few compiles
            rows = np.asarray(rows + [0] * pad, np.int32)
            cols = np.asarray(cols + [0] * pad, np.int32)
            ref = np.asarray(_logits_at(params, tokens, rows, cols, m=m, quant=False), np.float64)[:n]
            best = ref.max(-1)
            gaps.append(best - ref[np.arange(n), want])
            if control:
                low = np.asarray(_logits_at(params, tokens, rows, cols, m=m, quant=True))[:n]
                ctrl_gaps.append(best - ref[np.arange(n), low.argmax(-1)])
    gaps = np.concatenate(gaps) if gaps else np.zeros(0)
    if control:
        return gaps, (np.concatenate(ctrl_gaps) if ctrl_gaps else np.zeros(0))
    return gaps


def _layer_weights(cfg: dict) -> dict:
    """Weights per token of each part of a layer, the held routed experts
    at the share of them a token reaches on average:
    ``num_experts_per_tok * n_routed_experts / router_experts``."""
    d, h, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    f = cfg["moe_intermediate_size"]
    reached = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / cfg["router_experts"]
    return {
        "attention": d * h * (nope + rope) + d * (r + rope) + r * h * (nope + vd) + h * vd * d,
        "dense": 3 * d * cfg["intermediate_size"],
        "router": d * cfg["router_experts"],
        "shared": 3 * d * f * cfg["n_shared_experts"],
        "routed": reached * 3 * d * f,
    }


def request_flops(cfg: dict, prompt_len: int, generated: int) -> float:
    """One served request: a prefill of ``prompt_len`` tokens that yields
    the first token, then ``generated - 1`` single-token steps; matrix
    products of the published forward (expanded attention), two operations
    a multiply-add.

    Every token passes every layer's weights: attention, the dense GLU of
    the first layers, and in the others router, shared experts and the held
    routed experts at their expected share (``_layer_weights``: 6 x 8 / 64
    = 0.75 an MoE layer a token, as published). Attention adds, per layer,
    head and earlier position (causal), q.k over nope + rope dims and p.v
    over v dims. The output head runs once per produced token."""
    w = _layer_weights(cfg)
    n, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    per_token = n * w["attention"] + dense * w["dense"] + (n - dense) * (w["router"] + w["shared"] + w["routed"])
    tokens = prompt_len + max(generated - 1, 0)
    attended = sum(range(1, tokens + 1))
    dims = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    attention = 2.0 * n * cfg["num_attention_heads"] * dims * attended
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * max(generated, 1)
    return 2.0 * per_token * tokens + attention + head


def step_bytes(cfg: dict) -> float:
    """Bytes a batch-1 decode step must read: every layer's attention
    weights, the dense layers' GLU, the MoE layers' shared experts and the
    held routed experts a token reaches on average, the output head (in
    the configuration's ``dtype``), and the whole latent cache,
    ``kv_lora_rank + qk_rope_head_dim`` values a position at
    ``serve.max_seq`` positions a layer (in ``serve.cache_dtype``).
    Norms, the router and the one embedding row are left out (under 1%)."""
    w = _layer_weights(cfg)
    n, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    weights = n * w["attention"] + dense * w["dense"] + (n - dense) * (w["shared"] + w["routed"])
    weights += cfg["hidden_size"] * cfg["vocab_size"]
    sc = cfg["serve"]
    cache = n * sc["max_seq"] * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
    return float(
        weights * jnp.dtype(cfg["dtype"]).itemsize + cache * jnp.dtype(sc["cache_dtype"]).itemsize
    )
