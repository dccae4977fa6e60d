"""Operation counts against hand counts at small shapes, and the peak table."""

import pytest

from bench import flops


def test_seq2seq_forward_by_hand():
    cfg = {"d_embed": 2, "d_hidden": 3, "vocab_size": 5, "n_encoder_layers": 2}
    # batch 1, 4 abstract positions, title block of 3 (2 decoder steps)
    encoder = 4 * (2 * (2 + 3) * 12 + 2 * (3 + 3) * 12)  # x@wx + h@wh per layer
    keys = 2 * 4 * 3 * 3  # W_h h_j once per sequence
    per_step = (
        2 * (2 + 3) * 12  # decoder cell
        + 2 * 3 * 3  # W_s s_i
        + 2 * 4 * 3  # v . tanh(...) over 4 positions
        + 2 * 4 * 3  # context
        + 2 * 6 * 5  # output dense over [s; C]
    )
    want = encoder + keys + 2 * per_step
    assert flops.seq2seq_forward_flops(cfg, 1, 4, 3) == want
    assert flops.seq2seq_step_flops(cfg, 2, 4, 3) == 3 * 2 * want


def test_lm_request_by_hand():
    cfg = {"hidden_size": 4, "intermediate_size": 6, "vocab_size": 10, "num_hidden_layers": 2,
           "num_attention_heads": 2, "num_key_value_heads": 2}
    layer = 4 * 4 * 4 + 3 * 4 * 6  # q, k, v, o and gate, up, down
    # 3 prompt tokens and 3 generated: the prefill yields the first, two
    # single-token steps the others, so 5 tokens pass the layers
    tokens = 5
    attention = 4 * 2 * 4 * (1 + 2 + 3 + 4 + 5)
    head = 2 * 4 * 10 * 3
    assert flops.lm_request_flops(cfg, 3, 3) == 2 * 2 * layer * tokens + attention + head
    assert flops.lm_request_flops(cfg, 3, 1) == 2 * 2 * layer * 3 + 4 * 2 * 4 * 6 + 2 * 4 * 10


def test_peaks_known_and_unknown():
    assert flops.peak("TPU v5 lite") == 197e12
    assert flops.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        flops.peak("TPU v9 imaginary")
