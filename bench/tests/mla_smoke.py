"""The deepseek-v2-lite cell's configuration cut to a size a CPU test can run,
and the program configuration that matches it."""

import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def cfg(**over) -> dict:
    """``bench/configs/deepseek-v2-lite.json`` at the widths of the program's
    ``deepseek_v2_lite_ep8.SMOKE``: 8 routed experts, 2 held, top-2."""
    c = json.loads((BENCH / "configs" / "deepseek-v2-lite.json").read_text())
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4, num_hidden_layers=3,
             kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=12,
             intermediate_size=96, moe_intermediate_size=32, router_experts=8, n_routed_experts=2,
             num_experts_per_tok=2, vocab_size=64)
    c["rope_scaling"] = dict(c["rope_scaling"], factor=4, original_max_position_embeddings=32)
    c["serve"] = dict(c["serve"], vocab_corpus_mb=0.3, max_seq=160)
    c.update(over)
    return c


def arch(vocab_size: int = 64, **moe):
    import dataclasses

    from repro.configs.deepseek_v2_lite_ep8 import SMOKE

    return dataclasses.replace(SMOKE, vocab_size=vocab_size, moe=dataclasses.replace(SMOKE.moe, **moe))
