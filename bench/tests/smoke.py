"""Cell configurations cut to sizes a CPU test can run."""

import dataclasses
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def seq2seq_cfg(**over) -> dict:
    cfg = json.loads((BENCH / "configs" / "p3sapp-seq2seq.json").read_text())
    cfg.update(vocab_size=128, d_embed=16, d_hidden=32, n_encoder_layers=2,
               max_abstract_len=24, max_title_len=8, batch_size=8)
    cfg.update(over)
    return cfg


def train_traffic(name: str = "train_stream", **over) -> dict:
    tr = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
    tr.update(corpus_mb=0.3, sample_every=4)
    tr.update(over)
    return tr


def lm_cfg(hidden: int = 64, layers: int = 3, ff: int = 160, heads: int = 4) -> dict:
    cfg = json.loads((BENCH / "configs" / "stablelm-3b.json").read_text())
    cfg.update(hidden_size=hidden, intermediate_size=ff, num_hidden_layers=layers,
               num_attention_heads=heads, num_key_value_heads=heads, vocab_size=8192)
    cfg["serve"].update(vocab_corpus_mb=0.3, max_seq=160)
    return cfg


def lm_arch(hidden: int = 64, layers: int = 3, ff: int = 160, heads: int = 4):
    from repro.configs.stablelm_3b import SMOKE

    return dataclasses.replace(SMOKE, d_model=hidden, n_layers=layers, d_ff=ff, n_heads=heads,
                               n_kv_heads=heads, vocab_size=8192)


def serve_traffic(name: str = "serve_titles", **over) -> dict:
    tr = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
    tr.update(rate_per_s=2.0)
    tr.update(over)
    return tr
