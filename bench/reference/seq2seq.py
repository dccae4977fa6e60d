"""Plain reference of the summarizer's training step.

The paper's title generator (§4.2.3): a stacked LSTM encoder over the
abstract, one LSTM decoder started from the encoder's last state, Bahdanau
attention ``e_ij = v . tanh(W_s s_i + W_h h_j)``, the output dense layer
over ``[s_i; C_i]``, and teacher-forced cross entropy over the non-pad
targets. LSTM gates in the order (i, f, g, o), with 1 added to the forget
gate's input, as the system under test does (the paper does not say).

The optimizer is AdamW with global-norm clipping at 1 and a linear
warm-up into a cosine decay to a tenth of the peak rate.

Everything is float32 at ``highest`` matmul precision unless ``dtype``
asks for less: the control keeps the weights and computes forward and
backward in bfloat16 (the optimizer's moments stay float32).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

PAD = 0


def _tn(key, shape, scale):
    return scale * jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)


def _lstm_init(key, d_in, hidden, scale):
    k1, k2 = jax.random.split(key)
    return {
        "wx": _tn(k1, (d_in, 4 * hidden), scale / np.sqrt(d_in)),
        "wh": _tn(k2, (hidden, 4 * hidden), scale / np.sqrt(hidden)),
        "b": jnp.zeros((4 * hidden,), jnp.float32),
    }


def init_params(key, cfg: dict) -> dict:
    """Seeded float32 weights in the system's parameter layout."""
    v, e, h, n = cfg["vocab_size"], cfg["d_embed"], cfg["d_hidden"], cfg["n_encoder_layers"]
    s = cfg["init_scale"]
    ks = jax.random.split(key, 8 + n)
    enc, d_in = [], e
    for i in range(n):
        enc.append(_lstm_init(ks[i], d_in, h, s))
        d_in = h
    return {
        "embed_enc": _tn(ks[n], (v, e), 1.0),
        "embed_dec": _tn(ks[n + 1], (v, e), 1.0),
        "encoder": enc,
        "decoder": _lstm_init(ks[n + 2], e, h, s),
        "attn_ws": _tn(ks[n + 3], (h, h), s / np.sqrt(h)),
        "attn_wh": _tn(ks[n + 4], (h, h), s / np.sqrt(h)),
        "attn_v": _tn(ks[n + 5], (h,), s / np.sqrt(h)),
        "out_w": _tn(ks[n + 6], (2 * h, v), s / np.sqrt(2 * h)),
        "out_b": jnp.zeros((v,), jnp.float32),
    }


def _cell(p, x, h, c):
    z = x @ p["wx"] + h @ p["wh"] + p["b"]
    i, f, g, o = jnp.split(z.astype(jnp.float32), 4, axis=-1)
    c = jax.nn.sigmoid(f + 1.0) * c.astype(jnp.float32) + jax.nn.sigmoid(i) * jnp.tanh(g)
    h = jax.nn.sigmoid(o) * jnp.tanh(c)
    return h.astype(x.dtype), c.astype(x.dtype)


def _run_lstm(p, xs):
    b = xs.shape[0]
    hidden = p["wh"].shape[0]
    zero = jnp.zeros((b, hidden), xs.dtype)

    def step(carry, x):
        h, c = _cell(p, x, *carry)
        return (h, c), h

    (h, c), hs = jax.lax.scan(step, (zero, zero), jnp.swapaxes(xs, 0, 1))
    return jnp.swapaxes(hs, 0, 1), (h, c)


def loss(params: dict, batch: dict) -> jax.Array:
    """Mean cross entropy of the next title token over non-pad targets."""
    enc_tokens, dec_tokens = batch["encoder_tokens"], batch["decoder_tokens"]
    hs = params["embed_enc"][enc_tokens]
    for layer in params["encoder"]:
        hs, state = _run_lstm(layer, hs)
    mask = enc_tokens != PAD
    keys = hs @ params["attn_wh"]  # W_h h_j, once per sequence
    dec_in = params["embed_dec"][dec_tokens[:, :-1]]

    def step(carry, x):
        h, c = _cell(params["decoder"], x, *carry)
        e = jnp.tanh(((h @ params["attn_ws"])[:, None, :] + keys).astype(jnp.float32))
        e = e @ params["attn_v"].astype(jnp.float32)
        a = jax.nn.softmax(jnp.where(mask, e, -1e30), axis=-1).astype(hs.dtype)
        ctx = jnp.einsum("bs,bsh->bh", a, hs)
        logits = jnp.concatenate([h, ctx], axis=-1) @ params["out_w"] + params["out_b"]
        return (h, c), logits

    _, logits = jax.lax.scan(step, state, jnp.swapaxes(dec_in, 0, 1))
    logits = jnp.swapaxes(logits, 0, 1).astype(jnp.float32)
    targets = dec_tokens[:, 1:]
    keep = (targets != PAD).astype(jnp.float32)
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.sum(nll * keep) / jnp.maximum(keep.sum(), 1.0)


def learning_rate(count: int, peak: float, warmup: int, total: int, floor: float = 0.1) -> float:
    if count < warmup:
        return peak * count / max(warmup, 1)
    prog = min(max((count - warmup) / max(total - warmup, 1), 0.0), 1.0)
    return peak * (floor + (1 - floor) * 0.5 * (1 + math.cos(math.pi * prog)))


def train_steps(params: dict, batches: list[dict], opt: dict, dtype=jnp.float32):
    """Run len(batches) AdamW steps from ``params``. Returns the losses,
    the clipped gradient of the first step (what the optimizer got) and
    the parameters after the last step, all on the host."""
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]

    @jax.jit
    def grads_of(p, batch):
        value, g = jax.value_and_grad(loss)(p, batch)
        g = jax.tree.map(lambda x: x.astype(jnp.float32), g)
        norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(norm, 1e-9))
        return value, jax.tree.map(lambda x: x * scale, g)

    @jax.jit
    def apply(p, m, v, g, lr, c1, c2):
        m = jax.tree.map(lambda mm, gg: b1 * mm + (1 - b1) * gg, m, g)
        v = jax.tree.map(lambda vv, gg: b2 * vv + (1 - b2) * gg * gg, v, g)
        def upd(pp, mm, vv):
            pf = pp.astype(jnp.float32)
            return (pf - lr * ((mm / c1) / (jnp.sqrt(vv / c2) + eps) + wd * pf)).astype(dtype)

        return jax.tree.map(upd, p, m, v), m, v

    params = jax.tree.map(lambda x: x.astype(dtype), params)
    m = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
    v = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
    losses, first_grad = [], None
    with jax.default_matmul_precision("highest"):
        for k, batch in enumerate(batches, start=1):
            value, g = grads_of(params, batch)
            if first_grad is None:
                first_grad = jax.device_get(g)
            lr = learning_rate(k, opt["lr"], opt["warmup_steps"], opt["schedule_steps"])
            params, m, v = apply(params, m, v, g, lr, 1 - b1**k, 1 - b2**k)
            losses.append(float(value))
    return losses, first_grad, jax.device_get(params)
