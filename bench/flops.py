"""Operations the models need, from shapes, and the chip peaks they are held to.

Only matrix products are counted (two operations per multiply-add);
elementwise work, softmax and gathers are left out. A training step is
three times its forward pass (forward, and a backward of twice that).
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).with_name("peaks.json")


def peak(device_kind: str, key: str = "bf16_flops_per_s") -> float:
    """A published peak of one chip of ``device_kind``; an unknown kind is
    an error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} in {PEAKS.name}")
    return float(table[device_kind][key])


def seq2seq_forward_flops(cfg: dict, batch: int, enc_len: int, dec_len: int) -> float:
    """Forward pass of the title generator on a (batch, enc_len) abstract
    block and a (batch, dec_len) title block (dec_len - 1 decoder steps).

    Encoder: per position and layer, ``x @ wx`` and ``h @ wh``. Decoder,
    per step: the LSTM cell, ``s_i @ W_s``, the scores ``v . tanh(...)``
    and the context over the encoder positions, and the output dense over
    ``[s_i; C_i]``. The keys ``W_h h_j`` are counted once per sequence,
    as the model needs them, not once per decoder step."""
    e, h, v, n = cfg["d_embed"], cfg["d_hidden"], cfg["vocab_size"], cfg["n_encoder_layers"]
    steps = dec_len - 1
    encoder = enc_len * sum(2 * (d_in + h) * 4 * h for d_in in [e] + [h] * (n - 1))
    keys = 2 * enc_len * h * h
    per_step = 2 * (e + h) * 4 * h + 2 * h * h + 2 * enc_len * h * 2 + 2 * 2 * h * v
    return float(batch * (encoder + keys + steps * per_step))


def seq2seq_step_flops(cfg: dict, batch: int, enc_len: int, dec_len: int) -> float:
    return 3.0 * seq2seq_forward_flops(cfg, batch, enc_len, dec_len)


def lm_layer_params(cfg: dict) -> int:
    """Weights of one decoder layer that multiply every token."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = d // cfg["num_attention_heads"]
    attn = d * hd * (2 * cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"])
    return attn + 3 * d * f


def lm_request_flops(cfg: dict, prompt_len: int, generated: int) -> float:
    """One served request: a prefill of ``prompt_len`` tokens that yields
    the first token, then ``generated - 1`` single-token steps.

    Every token passes every layer's weights (2 x params). Attention adds,
    per layer and token, 2 x 2 x d_model per earlier position (scores and
    context, causal). The output head runs once per produced token."""
    d, v, n = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    tokens = prompt_len + max(generated - 1, 0)
    weights = 2.0 * n * lm_layer_params(cfg) * tokens
    attended = sum(range(1, tokens + 1))  # positions seen by each token, causal
    attention = 4.0 * n * d * attended
    head = 2.0 * d * v * max(generated, 1)
    return weights + attention + head
