"""Shared model building blocks (pure-JAX, functional params-as-pytrees).

Conventions
-----------
* Every parameter leaf is annotated in the matching ``*_axes`` pytree with a
  tuple of *logical axis names* (see repro.distributed.sharding). ``None``
  means replicated along that dim.
* Compute dtype follows the input; norms/softmax accumulate in fp32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def truncated_normal(key, shape, dtype, scale: float):
    return (scale * jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_norm(cfg, dtype) -> dict:
    p = {"scale": jnp.ones((cfg.d_model,), dtype)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((cfg.d_model,), dtype)
    return p


def norm_axes(cfg) -> dict:
    p = {"scale": ("embed",)}
    if cfg.norm == "layernorm":
        p["bias"] = ("embed",)
    return p


def apply_norm(p: dict, x: jax.Array, kind: str) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + 1e-6)
        return (out * p["scale"].astype(jnp.float32)).astype(x.dtype)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + 1e-5)
    out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (GLU or plain)
# ---------------------------------------------------------------------------

_ACTS = {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}


def init_mlp(key, cfg, d_ff: int | None = None, dtype=jnp.bfloat16) -> dict:
    d_ff = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    scale = cfg.init_scale / np.sqrt(cfg.d_model)
    p = {"down": truncated_normal(k2, (d_ff, cfg.d_model), dtype, cfg.init_scale / np.sqrt(d_ff))}
    if cfg.glu:
        p["gate"] = truncated_normal(k1, (cfg.d_model, d_ff), dtype, scale)
        p["up"] = truncated_normal(k3, (cfg.d_model, d_ff), dtype, scale)
    else:
        p["up"] = truncated_normal(k1, (cfg.d_model, d_ff), dtype, scale)
    return p


def mlp_axes(cfg) -> dict:
    p = {"down": ("mlp", "embed"), "up": ("embed", "mlp")}
    if cfg.glu:
        p["gate"] = ("embed", "mlp")
    return p


def apply_mlp(p: dict, x: jax.Array, cfg) -> jax.Array:
    act = _ACTS[cfg.act]
    if cfg.glu:
        h = act(x @ p["gate"]) * (x @ p["up"])
    else:
        h = act(x @ p["up"])
    return h @ p["down"]


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def init_embed(key, cfg, dtype) -> dict:
    k1, k2 = jax.random.split(key)
    p = {"embedding": truncated_normal(k1, (cfg.vocab_size, cfg.d_model), dtype, 1.0)}
    if not cfg.tie_embeddings:
        p["lm_head"] = truncated_normal(
            k2, (cfg.d_model, cfg.vocab_size), dtype, cfg.init_scale / np.sqrt(cfg.d_model)
        )
    return p


def embed_axes(cfg) -> dict:
    p = {"embedding": ("vocab", "embed")}
    if not cfg.tie_embeddings:
        p["lm_head"] = ("embed", "vocab")
    return p


def embed_tokens(p: dict, tokens: jax.Array, cfg) -> jax.Array:
    x = jnp.take(p["embedding"], tokens, axis=0)
    if not cfg.embed_scale:
        return x
    return x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)


def lm_logits(p: dict, x: jax.Array, cfg) -> jax.Array:
    if cfg.tie_embeddings:
        logits = x @ p["embedding"].T
        if not cfg.embed_scale:
            return logits
        # un-scale: embed_tokens multiplied by sqrt(d); keep logits O(1)
        return logits / jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    return x @ p["lm_head"]


# ---------------------------------------------------------------------------
# RoPE (standard + M-RoPE)
# ---------------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term: 0.1 * mscale * ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(scaling, head_dim: int, theta: float) -> tuple[int, int]:
    """The frequency pairs (low, high) between which YaRN ramps from the
    unscaled frequencies (below low) to the interpolated ones (above high):
    pair i turns ``beta`` times in ``original_max_position`` positions at
    i = head_dim * ln(L / (2 pi beta)) / (2 ln theta)."""

    def pair(beta: float) -> float:
        return head_dim * math.log(scaling.original_max_position / (beta * 2 * math.pi)) / (
            2 * math.log(theta)
        )

    low = math.floor(pair(scaling.beta_fast))
    high = math.ceil(pair(scaling.beta_slow))
    return max(low, 0), min(high, head_dim - 1)


def rope_frequencies(head_dim: int, theta: float = 10000.0, scaling=None) -> np.ndarray:
    freqs = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))
    if scaling is None:
        return freqs
    low, high = yarn_correction_range(scaling, head_dim, theta)
    ramp = np.clip((np.arange(head_dim // 2) - low) / max(high - low, 0.001), 0.0, 1.0)
    return freqs / scaling.factor * ramp + freqs * (1.0 - ramp)


def apply_rope(
    x: jax.Array, positions: jax.Array, theta: float = 10000.0, scaling=None
) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq).
    With ``scaling`` (a YarnScaling), YaRN's frequencies, and cos and sin
    times mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
    head_dim = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(head_dim, theta, scaling), jnp.float32)
    angles = positions[..., :, None, None].astype(jnp.float32) * freqs  # (..., s, 1, hd/2)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    if scaling is not None:
        m = yarn_mscale(scaling.factor, scaling.mscale) / yarn_mscale(
            scaling.factor, scaling.mscale_all_dim
        )
        sin, cos = sin * m, cos * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jax.Array, positions_3d: jax.Array, sections: tuple[int, int, int], theta: float = 1e6
) -> jax.Array:
    """Qwen2-VL multimodal RoPE.

    ``positions_3d``: (3, ..., seq) temporal/height/width position streams.
    ``sections``: frequency-split sizes (in half-dim units) per stream.
    For pure-text positions all three streams are equal, which reduces to
    standard RoPE.
    """
    head_dim = x.shape[-1]
    half = head_dim // 2
    freqs = jnp.asarray(rope_frequencies(head_dim, theta), jnp.float32)  # (half,)
    # stream id per frequency slot
    sid = np.zeros(half, dtype=np.int32)
    sid[sections[0] : sections[0] + sections[1]] = 1
    sid[sections[0] + sections[1] :] = 2
    pos = jnp.take(positions_3d, jnp.asarray(sid), axis=0)  # (half, ..., seq)
    pos = jnp.moveaxis(pos, 0, -1)  # (..., seq, half)
    angles = pos[..., None, :].astype(jnp.float32) * freqs  # (..., seq, 1, half)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def cross_entropy_loss(logits: jax.Array, targets: jax.Array, mask: jax.Array) -> jax.Array:
    """Mean CE over mask (fp32 accumulation)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - gold
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
