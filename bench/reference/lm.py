"""Plain reference of the served decoder LM, and its float8 control.

The configuration's model as the system under test defines it: token
embedding times sqrt(d_model); per layer a pre-LayerNorm (eps 1e-5) causal
multi-head attention with rotary position embedding on the whole head
(theta 10000, halves rotated against each other) and a pre-LayerNorm GLU
MLP ``down(silu(gate x) * up x)``, each added to the residual; a final
LayerNorm and an untied output head. Full causal attention over the whole
sequence, no cache and no batching tricks: one forward pass per block of
right-padded sequences, computed layer by layer in float32 at ``highest``
matmul precision from the served bfloat16 weights.

``quant=True`` is the control: every linear layer's weight (per output
channel) and input (per token) rounded to float8 e4m3, scaled so that the
largest magnitude of each lands on the format's largest number.

A serving configuration names this module with ``"reference": "lm"``;
the serve driver takes from it the four names every reference module
gives: ``PROGRAM_KEYS``, ``init_params``, ``request_flops`` and
``served_gaps``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops

# configuration file key -> the attribute of the program's ArchConfig that
# must equal it (a dotted path reaches into a nested group)
PROGRAM_KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "use_qkv_bias": "qkv_bias",
    "tie_word_embeddings": "tie_embeddings",
}

BLOCK = 8  # sequences a reference forward pass takes at once

request_flops = flops.lm_request_flops


def init_params(key, cfg: dict) -> dict:
    """Seeded weights in the system's parameter layout (layers stacked on a
    leading axis), made in the configuration's ``dtype``. Each matrix is
    normal with std 1/sqrt(fan_in); the two projections back into the
    residual are scaled by 1/sqrt(2 * layers) so the residual stream stays
    O(1) over depth; the embedding has std 1/sqrt(d_model), so that after
    the sqrt(d_model) scale a token enters the residual at unit scale."""
    d, f, v, n = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    nh = cfg["num_attention_heads"]
    dtype = getattr(jnp, cfg["dtype"])
    hd = d // nh
    ks = iter(jax.random.split(key, 16))
    res = 1.0 / np.sqrt(2 * n)

    def normal(shape, std):
        return (std * jax.random.normal(next(ks), shape, jnp.float32)).astype(dtype)

    ones = jnp.ones((n, d), dtype)
    zeros = jnp.zeros((n, d), dtype)
    layer = {
        "norm1": {"scale": ones, "bias": zeros},
        "attn": {
            "wq": normal((n, d, nh, hd), d**-0.5),
            "wk": normal((n, d, nh, hd), d**-0.5),
            "wv": normal((n, d, nh, hd), d**-0.5),
            "wo": normal((n, nh, hd, d), res * d**-0.5),
        },
        "norm2": {"scale": ones, "bias": zeros},
        "mlp": {
            "gate": normal((n, d, f), d**-0.5),
            "up": normal((n, d, f), d**-0.5),
            "down": normal((n, f, d), res * f**-0.5),
        },
    }
    return {
        "embed": {"embedding": normal((v, d), d**-0.5), "lm_head": normal((d, v), d**-0.5)},
        "head": [],
        "units": [layer],
        "tail": [],
        "final_norm": {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)},
    }


def _q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / float(jnp.finfo(jnp.float8_e4m3fn).max)
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _linear(x, w, quant, w_in_axes):
    """x (..., in) times w whose input axes are ``w_in_axes``."""
    if quant:
        x = _q8(x, -1)
        w = _q8(w, w_in_axes)
    return x, w


def _norm(x, p):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + 1e-5) * p["scale"] + p["bias"]


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, :, None, None].astype(jnp.float32) * freqs
    x1, x2 = x[..., : hd // 2], x[..., hd // 2 :]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang), x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _layer(x, p, *, quant, theta):
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    b, s, _ = x.shape
    h = _norm(x, p["norm1"])
    a = p["attn"]
    hq, wq = _linear(h, a["wq"], quant, 0)
    _, wk = _linear(h, a["wk"], quant, 0)
    _, wv = _linear(h, a["wv"], quant, 0)
    q = jnp.einsum("bsd,dhk->bshk", hq, wq)
    k = jnp.einsum("bsd,dhk->bshk", hq, wk)
    v = jnp.einsum("bsd,dhk->bshk", hq, wv)
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    scores = jnp.einsum("bshk,bthk->bhst", q, k) / np.sqrt(q.shape[-1])
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    o = jnp.einsum("bhst,bthk->bshk", probs, v)
    o, wo = _linear(o.reshape(b, s, -1), a["wo"].reshape(-1, a["wo"].shape[-1]), quant, 0)
    x = x + o @ wo
    h = _norm(x, p["norm2"])
    m = p["mlp"]
    hg, wg = _linear(h, m["gate"], quant, 0)
    _, wu = _linear(h, m["up"], quant, 0)
    inner = jax.nn.silu(hg @ wg) * (hg @ wu)
    inner, wd = _linear(inner, m["down"], quant, 0)
    return x + inner @ wd


@partial(jax.jit, static_argnames=("quant", "theta"))
def _logits_at(params, tokens, rows, cols, *, quant, theta):
    """Logits (float32) at positions (rows[i], cols[i]) of ``tokens``."""
    emb = params["embed"]["embedding"]
    x = emb[tokens].astype(jnp.float32) * np.sqrt(emb.shape[-1])

    def body(x, layer):
        return _layer(x, layer, quant=quant, theta=theta), None

    x, _ = jax.lax.scan(body, x, params["units"][0])
    fn = jax.tree.map(lambda a: a.astype(jnp.float32), params["final_norm"])
    h = _norm(x[rows, cols], fn)
    h, w = _linear(h, params["embed"]["lm_head"].astype(jnp.float32), quant, 0)
    return h @ w


def served_gaps(params, prompts, served, cfg: dict, *, control: bool = False):
    """For each served token, how far the reference's logit of it lies
    below the reference's best logit at that position.

    ``prompts[i]`` and ``served[i]`` are the token ids of request i. The
    token ``served[i][j]`` was produced at position ``len(prompts[i]) - 1 + j``
    of the sequence ``prompts[i] + served[i][:-1]``. The rotary base is the
    configuration's ``rope_theta``. Returns the gaps as one float64 array;
    with ``control`` also the gaps of the tokens that the float8 control
    puts first at the same positions."""
    theta, block = cfg["rope_theta"], BLOCK
    order = sorted(range(len(prompts)), key=lambda i: len(prompts[i]) + len(served[i]))
    gaps, ctrl_gaps = [], []
    with jax.default_matmul_precision("highest"):
        for start in range(0, len(order), block):
            idx = order[start : start + block]
            seqs = [list(prompts[i]) + list(served[i][:-1]) for i in idx]
            width = -(-max(len(s) for s in seqs) // 64) * 64
            tokens = np.zeros((block, width), np.int32)
            rows, cols, want = [], [], []
            for r, i in enumerate(idx):
                tokens[r, : len(seqs[r])] = seqs[r]
                for j, t in enumerate(served[i]):
                    rows.append(r)
                    cols.append(len(prompts[i]) - 1 + j)
                    want.append(t)
            n = len(want)
            pad = -(-n // 128) * 128 - n  # few position counts, few compiles
            rows = np.asarray(rows + [0] * pad, np.int32)
            cols = np.asarray(cols + [0] * pad, np.int32)
            ref = np.asarray(_logits_at(params, tokens, rows, cols, quant=False, theta=theta), np.float64)[:n]
            best = ref.max(-1)
            gaps.append(best - ref[np.arange(n), want])
            if control:
                low = np.asarray(_logits_at(params, tokens, rows, cols, quant=True, theta=theta))[:n]
                ctrl_gaps.append(best - ref[np.arange(n), low.argmax(-1)])
    gaps = np.concatenate(gaps) if gaps else np.zeros(0)
    if control:
        return gaps, (np.concatenate(ctrl_gaps) if ctrl_gaps else np.zeros(0))
    return gaps
