"""Architecture + shape configuration registry.

Every assigned architecture has a ``<id>.py`` module exporting ``CONFIG``
(the exact published configuration) and ``SMOKE`` (a reduced same-family
config for CPU tests). ``get(name)`` returns the full config,
``get_smoke(name)`` the reduced one. ``SHAPES`` are the assigned input
shapes; per-arch applicability (``supported_shapes``) encodes the
assignment sheet's skip rules.
"""

from __future__ import annotations

import dataclasses
import importlib
from dataclasses import dataclass
from typing import Any


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int  # routed experts
    top_k: int
    d_expert: int  # per-expert FFN width
    n_shared: int = 0
    first_k_dense: int = 0  # leading layers with a dense FFN instead of MoE
    d_ff_dense: int = 0  # width of those dense FFNs
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    expert_impl: str = "ragged"  # "ragged" | "batched" (see models.moe)
    norm_topk_prob: bool = True  # renormalise the top-k weights to sum to 1
    routed_scaling_factor: float = 1.0  # multiplies the routed experts' weights
    # the share of the routed experts this chip holds: experts first_held ..
    # first_held + held - 1 (held 0: all of them). The router still scores
    # all n_experts; the others' part of the output lies on other chips.
    held: int = 0
    first_held: int = 0

    @property
    def n_held(self) -> int:
        return self.held or self.n_experts


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2), without query compression:
    keys and values come from one ``kv_lora_rank``-wide latent per token;
    each head's query and key are ``qk_nope_head_dim`` unrotated dims plus
    ``qk_rope_head_dim`` rotary dims, the rotary key shared by all heads."""

    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclass(frozen=True)
class YarnScaling:
    """YaRN rotary scaling (arXiv:2309.00071, as DeepSeek-V2 configures it)."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str  # dense | moe | hybrid | ssm | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // n_heads
    moe: MoEConfig | None = None
    qkv_bias: bool = False
    rope: str = "rope"  # rope | mrope | none
    rope_theta: float = 10000.0
    rope_scaling: YarnScaling | None = None  # YaRN; read by latent attention (mla) only
    mla: MLAConfig | None = None  # set: latent attention in place of MHA
    embed_scale: bool = True  # multiply the token embedding by sqrt(d_model)
    causal: bool = True  # False -> encoder-only (hubert)
    window: int = 0  # >0 -> sliding-window attention
    block_pattern: tuple[str, ...] = ("attn",)  # unit scanned over depth
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    act: str = "silu"
    glu: bool = True
    tie_embeddings: bool = False
    frontend: str = ""  # "" | audio | vision (modality stub)
    frontend_dim: int = 0  # stub embedding dim
    n_frontend_tokens: int = 256  # patches/frames occupying the seq head
    d_rnn: int = 0  # recurrent width for rglru/xlstm blocks (0 -> d_model)
    init_scale: float = 0.02
    # flash-style jnp attention chunk sizes (0 q_chunk = no query chunking,
    # kv-only streaming — required by the sequence-parallel plan)
    attn_q_chunk: int = 512
    attn_kv_chunk: int = 1024
    # sliding-window ring-buffer KV cache (§Perf; exact). False reproduces
    # the recorded full-cache baseline.
    ring_kv: bool = True
    notes: str = ""

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def resolved_d_rnn(self) -> int:
        return self.d_rnn or self.d_model

    @property
    def is_decoder(self) -> bool:
        return self.causal

    @property
    def is_subquadratic(self) -> bool:
        """False iff the arch contains unwindowed full attention."""
        return not ("attn" in self.block_pattern and self.window == 0)

    # -- analytic parameter counts (used by rooflines: 6·N·D) --------------
    def _attn_params(self) -> int:
        if self.mla is not None:
            a, h = self.mla, self.n_heads
            return (
                self.d_model * h * a.qk_head_dim  # q
                + self.d_model * (a.kv_lora_rank + a.qk_rope_head_dim)  # latent + rotary key
                + a.kv_lora_rank * h * (a.qk_nope_head_dim + a.v_head_dim)  # latent -> k, v
                + h * a.v_head_dim * self.d_model  # out
            )
        d, hd = self.d_model, self.resolved_head_dim
        n_q, n_kv = self.n_heads * hd, self.n_kv_heads * hd
        p = d * n_q + 2 * d * n_kv + n_q * d
        if self.qkv_bias:
            p += n_q + 2 * n_kv
        return p

    def _mlp_params(self, d_ff: int) -> int:
        return (3 if self.glu else 2) * self.d_model * d_ff

    def _block_params(self, kind: str) -> int:
        d, dr = self.d_model, self.resolved_d_rnn
        if kind == "attn":
            if self.moe is not None:
                m = self.moe
                experts = (m.n_held + m.n_shared) * self._mlp_params_w(m.d_expert)
                return self._attn_params() + experts + d * m.n_experts
            return self._attn_params() + self._mlp_params(self.d_ff)
        if kind == "rglru":
            # in/gate proj, out proj, conv4, rg-lru gates + lambda, plus MLP
            rec = 2 * d * dr + dr * d + 4 * dr + 2 * dr * dr + dr
            return rec + self._mlp_params(self.d_ff)
        if kind == "mlstm":
            # up-proj to 2*dr, qkv from dr, gates, down-proj
            return d * 2 * dr + 3 * dr * dr // 1 + 2 * dr + dr * d
        if kind == "slstm":
            return 4 * d * dr + 4 * dr * dr + 4 * dr + dr * d
        raise ValueError(kind)

    def _mlp_params_w(self, d_ff: int) -> int:
        return self._mlp_params(d_ff)

    def param_count(self) -> int:
        total = self.vocab_size * self.d_model
        if not self.tie_embeddings:
            total += self.d_model * self.vocab_size
        if self.frontend:
            total += self.frontend_dim * self.d_model
        if self.moe is not None:
            m = self.moe
            dense_layer = self._attn_params() + self._mlp_params(m.d_ff_dense)
            moe_layer = self._block_params("attn")
            return total + m.first_k_dense * dense_layer + (
                self.n_layers - m.first_k_dense
            ) * moe_layer
        pat = self.block_pattern
        n_units, rem = divmod(self.n_layers, len(pat))
        for i, kind in enumerate(pat):
            total += (n_units + (1 if i < rem else 0)) * self._block_params(kind)
        return total

    def active_param_count(self) -> int:
        """Activated parameters per token (MoE: top_k + shared only; of a
        held share, the top_k * n_held / n_experts held experts a token
        reaches on average)."""
        if self.moe is None:
            return self.param_count()
        m = self.moe
        reached = m.top_k * m.n_held / m.n_experts
        inactive = (self.n_layers - m.first_k_dense) * (
            m.n_held - reached
        ) * self._mlp_params(m.d_expert)
        return self.param_count() - round(inactive)


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}

ARCH_IDS = [
    "hubert_xlarge",
    "deepseek_moe_16b",
    "kimi_k2_1t_a32b",
    "stablelm_3b",
    "command_r_plus_104b",
    "granite_20b",
    "qwen2_5_32b",
    "recurrentgemma_9b",
    "xlstm_1_3b",
    "qwen2_vl_72b",
    "deepseek_v2_lite",
]


def _module(name: str):
    return importlib.import_module(f"repro.configs.{name.replace('-', '_')}")


def get(name: str) -> ArchConfig:
    return _module(name).CONFIG


def get_smoke(name: str) -> ArchConfig:
    return _module(name).SMOKE


def supported_shapes(cfg: ArchConfig) -> list[str]:
    """Assignment-sheet applicability (skips recorded in DESIGN.md §4)."""
    out = ["train_4k", "prefill_32k"]
    if cfg.is_decoder:
        out.append("decode_32k")
        if cfg.is_subquadratic:
            out.append("long_500k")
    return out


def all_cells() -> list[tuple[str, str]]:
    """Every runnable (arch, shape) pair — the dry-run/roofline grid."""
    cells = []
    for arch in ARCH_IDS:
        cfg = get(arch)
        for s in supported_shapes(cfg):
            cells.append((arch, s))
    return cells
