"""TransformerLM: one scan-over-layers model covering all assigned families.

Layer layout = ``head`` (unrolled leading layers, e.g. MoE first-k-dense) +
``units`` (the repeating block pattern, parameters stacked over units and
scanned — compile time is O(1) in depth) + ``tail`` (unrolled remainder when
n_layers % len(pattern) != 0).

Block kinds: ``attn`` (attention + MLP/MoE), ``rglru`` (Griffin recurrent +
MLP), ``mlstm``/``slstm`` (xLSTM, self-contained). Frontends: ``audio``
(HuBERT-style precomputed frame embeddings replace token embedding) and
``vision`` (Qwen2-VL-style patch embeddings occupy the first
``n_frontend_tokens`` positions; M-RoPE).
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import attention as A
from . import moe as MOE
from . import rglru as RG
from . import xlstm as XL
from .blocks import (
    apply_mlp,
    apply_norm,
    cross_entropy_loss,
    embed_axes,
    embed_tokens,
    init_embed,
    init_mlp,
    init_norm,
    lm_logits,
    mlp_axes,
    norm_axes,
    truncated_normal,
)


class MeshContext(NamedTuple):
    """Static distribution context threaded through the model."""

    mesh: Any = None
    data_axes: tuple[str, ...] = ()
    model_axis: str = ""
    seq_axis: str = ""  # set by the sequence-parallel plan

    def constrain_batch(self, x: jax.Array) -> jax.Array:
        """Anchor activation sharding: batch over the DP axes (+ optionally
        sequence over the model axis for the SP plan)."""
        if self.mesh is None or not self.data_axes:
            return x
        from jax.sharding import NamedSharding, PartitionSpec as P

        axes = self.data_axes if len(self.data_axes) > 1 else self.data_axes[0]
        if x.shape[0] % int(np.prod([self.mesh.shape[a] for a in self.data_axes])) != 0:
            return x
        rest = [None] * (x.ndim - 1)
        if (
            self.seq_axis
            and x.ndim >= 2
            and x.shape[1] % self.mesh.shape[self.seq_axis] == 0
        ):
            rest[0] = self.seq_axis
        spec = P(axes, *rest)
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))


# ---------------------------------------------------------------------------
# Per-block init / axes / apply
# ---------------------------------------------------------------------------


def _init_block(key, cfg, kind: str, moe_layer: bool, dtype) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    if kind == "attn":
        init_attn = A.init_mla if cfg.mla is not None else A.init_attention
        p = {"norm1": init_norm(cfg, dtype), "attn": init_attn(k1, cfg, dtype),
             "norm2": init_norm(cfg, dtype)}
        if moe_layer:
            p["moe"] = MOE.init_moe(k2, cfg, dtype)
        else:
            d_ff = cfg.moe.d_ff_dense if cfg.moe is not None else cfg.d_ff
            p["mlp"] = init_mlp(k2, cfg, d_ff=d_ff, dtype=dtype)
        return p
    if kind == "rglru":
        return {
            "norm1": init_norm(cfg, dtype), "rec": RG.init_rglru(k1, cfg, dtype),
            "norm2": init_norm(cfg, dtype), "mlp": init_mlp(k2, cfg, dtype=dtype),
        }
    if kind == "mlstm":
        return {"norm1": init_norm(cfg, dtype), "mix": XL.init_mlstm(k1, cfg, dtype)}
    if kind == "slstm":
        return {"norm1": init_norm(cfg, dtype), "mix": XL.init_slstm(k1, cfg, dtype)}
    raise ValueError(kind)


def _block_axes(cfg, kind: str, moe_layer: bool) -> dict:
    if kind == "attn":
        attn_axes = A.mla_axes if cfg.mla is not None else A.attention_axes
        p = {"norm1": norm_axes(cfg), "attn": attn_axes(cfg), "norm2": norm_axes(cfg)}
        if moe_layer:
            p["moe"] = MOE.moe_axes(cfg)
        else:
            p["mlp"] = mlp_axes(cfg)
        return p
    if kind == "rglru":
        return {"norm1": norm_axes(cfg), "rec": RG.rglru_axes(cfg),
                "norm2": norm_axes(cfg), "mlp": mlp_axes(cfg)}
    if kind == "mlstm":
        return {"norm1": norm_axes(cfg), "mix": XL.mlstm_axes(cfg)}
    if kind == "slstm":
        return {"norm1": norm_axes(cfg), "mix": XL.slstm_axes(cfg)}
    raise ValueError(kind)


def _apply_block(
    p: dict,
    x: jax.Array,
    cfg,
    kind: str,
    moe_layer: bool,
    mctx: MeshContext,
    *,
    positions: jax.Array,
    cache=None,
    cache_pos=0,
    layer=None,
):
    """Returns (x, new_cache, aux). ``layer``: see :func:`moe.apply_moe`."""
    aux = jnp.zeros((), jnp.float32)
    if kind == "attn":
        attend = A.attend_mla if cfg.mla is not None else A.attend
        h, new_cache = attend(
            p["attn"], apply_norm(p["norm1"], x, cfg.norm), cfg,
            positions=positions, cache=cache, cache_pos=cache_pos,
        )
        x = x + h
        h2 = apply_norm(p["norm2"], x, cfg.norm)
        if moe_layer:
            ff, aux = MOE.apply_moe(
                p["moe"], h2, cfg, mesh=mctx.mesh,
                data_axes=mctx.data_axes, model_axis=mctx.model_axis, layer=layer,
            )
        else:
            ff = apply_mlp(p["mlp"], h2, cfg)
        return x + ff, new_cache, aux
    if kind == "rglru":
        h, new_state = RG.apply_rglru_mix(p["rec"], apply_norm(p["norm1"], x, cfg.norm), cfg, state=cache)
        x = x + h
        x = x + apply_mlp(p["mlp"], apply_norm(p["norm2"], x, cfg.norm), cfg)
        return x, new_state, aux
    if kind == "mlstm":
        h, new_state = XL.mlstm_scan(p["mix"], apply_norm(p["norm1"], x, cfg.norm), cfg,
                                     state=cache if cache is not None else None)
        return x + h, new_state, aux
    if kind == "slstm":
        h, new_state = XL.slstm_scan(p["mix"], apply_norm(p["norm1"], x, cfg.norm), cfg,
                                     state=cache if cache is not None else None)
        return x + h, new_state, aux
    raise ValueError(kind)


def _hold_out_experts(p: dict, moe_layer: bool) -> tuple[dict, dict]:
    """A stacked block's params without its routed experts, and those."""
    if not moe_layer:
        return p, {}
    rest = {k: v for k, v in p["moe"].items() if k not in MOE.EXPERT_KEYS}
    return dict(p, moe=rest), {k: p["moe"][k] for k in MOE.EXPERT_KEYS}


def _init_block_cache(cfg, kind: str, batch: int, max_seq: int, dtype):
    if kind == "attn":
        if cfg.mla is not None:
            return A.init_mla_cache(batch, max_seq, cfg, dtype)
        return A.init_kv_cache(batch, max_seq, cfg, dtype)
    if kind == "rglru":
        return RG.init_rglru_state(batch, cfg, dtype)
    if kind == "mlstm":
        return XL.init_mlstm_state(batch, cfg)
    if kind == "slstm":
        return XL.init_slstm_state(batch, cfg)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


class LM:
    def __init__(self, cfg, mctx: MeshContext | None = None, *, remat: bool = True,
                 dtype=jnp.float32):
        self.cfg = cfg
        self.mctx = mctx or MeshContext()
        self.remat = remat
        self.dtype = dtype
        # layer layout
        pat = cfg.block_pattern
        if cfg.moe is not None:
            self.head_kinds = ["attn"] * cfg.moe.first_k_dense
            self.head_moe = [False] * cfg.moe.first_k_dense
            self.n_units = cfg.n_layers - cfg.moe.first_k_dense
            self.unit_pattern = ("attn",)
            self.unit_moe = (True,)
            self.tail_kinds: list[str] = []
            self.tail_moe: list[bool] = []
        else:
            self.head_kinds, self.head_moe = [], []
            self.n_units, rem = divmod(cfg.n_layers, len(pat))
            self.unit_pattern = pat
            self.unit_moe = tuple(False for _ in pat)
            self.tail_kinds = list(pat[:rem])
            self.tail_moe = [False] * rem

    # -- init ---------------------------------------------------------------
    def init(self, key) -> dict:
        cfg, dtype = self.cfg, self.dtype
        keys = jax.random.split(key, 8)
        params: dict = {"embed": init_embed(keys[0], cfg, dtype)}
        if cfg.frontend:
            params["frontend"] = {
                "proj": truncated_normal(
                    keys[1], (cfg.frontend_dim, cfg.d_model), dtype,
                    cfg.init_scale / np.sqrt(cfg.frontend_dim),
                )
            }
        params["head"] = [
            _init_block(k, cfg, kind, moe, dtype)
            for k, kind, moe in zip(
                jax.random.split(keys[2], max(len(self.head_kinds), 1)),
                self.head_kinds, self.head_moe,
            )
        ]
        unit_params = []
        for i, (kind, moe) in enumerate(zip(self.unit_pattern, self.unit_moe)):
            ks = jax.random.split(jax.random.fold_in(keys[3], i), self.n_units)
            unit_params.append(
                jax.vmap(lambda k: _init_block(k, cfg, kind, moe, dtype))(ks)
            )
        params["units"] = unit_params
        params["tail"] = [
            _init_block(k, cfg, kind, moe, dtype)
            for k, kind, moe in zip(
                jax.random.split(keys[4], max(len(self.tail_kinds), 1)),
                self.tail_kinds, self.tail_moe,
            )
        ]
        params["final_norm"] = init_norm(cfg, dtype)
        return params

    def param_axes(self) -> dict:
        """Logical-axis annotations, same tree structure as init()."""
        cfg = self.cfg
        axes: dict = {"embed": embed_axes(cfg)}
        if cfg.frontend:
            axes["frontend"] = {"proj": ("frontend", "embed")}
        axes["head"] = [_block_axes(cfg, k, m) for k, m in zip(self.head_kinds, self.head_moe)]
        axes["units"] = [
            jax.tree.map(lambda a: (None,) + a if isinstance(a, tuple) else a,
                         _block_axes(cfg, k, m), is_leaf=lambda a: isinstance(a, tuple))
            for k, m in zip(self.unit_pattern, self.unit_moe)
        ]
        axes["tail"] = [_block_axes(cfg, k, m) for k, m in zip(self.tail_kinds, self.tail_moe)]
        axes["final_norm"] = norm_axes(cfg)
        return axes

    # -- embedding / frontend -------------------------------------------------
    def _embed(self, params: dict, batch: dict) -> jax.Array:
        cfg = self.cfg
        if cfg.frontend == "audio":
            return batch["frames"].astype(self.dtype) @ params["frontend"]["proj"]
        x = embed_tokens(params["embed"], batch["tokens"], cfg)
        if cfg.frontend == "vision" and "patches" in batch:
            pe = batch["patches"].astype(x.dtype) @ params["frontend"]["proj"]
            n = pe.shape[1]
            x = jnp.concatenate([pe, x[:, n:]], axis=1)
        return x

    # -- full-sequence forward ------------------------------------------------
    def forward(self, params: dict, batch: dict) -> tuple[jax.Array, jax.Array]:
        """Returns (logits, moe_aux)."""
        cfg, mctx = self.cfg, self.mctx
        x = mctx.constrain_batch(self._embed(params, batch))
        b, s = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        aux_total = jnp.zeros((), jnp.float32)

        for p, kind, moe in zip(params["head"], self.head_kinds, self.head_moe):
            x, _, aux = _apply_block(p, x, cfg, kind, moe, mctx, positions=positions)
            aux_total += aux

        if self.n_units:
            def unit_fn(carry, unit_p):
                x = carry
                aux_sum = jnp.zeros((), jnp.float32)
                for p, kind, moe in zip(unit_p, self.unit_pattern, self.unit_moe):
                    x, _, aux = _apply_block(p, x, cfg, kind, moe, mctx, positions=positions)
                    aux_sum += aux
                return x, aux_sum

            body = jax.checkpoint(unit_fn) if self.remat else unit_fn
            x, auxs = jax.lax.scan(body, x, tuple(params["units"]))
            aux_total += auxs.sum()

        for p, kind, moe in zip(params["tail"], self.tail_kinds, self.tail_moe):
            x, _, aux = _apply_block(p, x, cfg, kind, moe, mctx, positions=positions)
            aux_total += aux

        x = apply_norm(params["final_norm"], x, cfg.norm)
        return lm_logits(params["embed"], x, cfg), aux_total

    def loss(self, params: dict, batch: dict) -> jax.Array:
        cfg = self.cfg
        logits, aux = self.forward(params, batch)
        if cfg.causal:
            targets = batch["tokens"][:, 1:]
            logits = logits[:, :-1]
            mask = jnp.ones_like(targets)
        else:  # encoder-only: per-position classification (HuBERT targets)
            targets = batch["labels"]
            mask = jnp.ones_like(targets)
        ce = cross_entropy_loss(logits, targets, mask)
        w = cfg.moe.router_aux_weight if cfg.moe is not None else 0.0
        return ce + w * aux

    # -- decode ---------------------------------------------------------------
    def init_decode_state(self, batch: int, max_seq: int, cache_dtype=jnp.bfloat16) -> dict:
        cfg = self.cfg
        state: dict = {
            "head": [
                _init_block_cache(cfg, k, batch, max_seq, cache_dtype) for k in self.head_kinds
            ],
            "tail": [
                _init_block_cache(cfg, k, batch, max_seq, cache_dtype) for k in self.tail_kinds
            ],
        }
        units = []
        for kind in self.unit_pattern:
            one = _init_block_cache(cfg, kind, batch, max_seq, cache_dtype)
            units.append(jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (self.n_units,) + a.shape), one
            ))
        state["units"] = units
        return state

    def decode_state_axes(self) -> dict:
        """Logical-axis annotations matching init_decode_state structure."""
        cfg = self.cfg

        def block_axes(kind: str):
            if kind == "attn":
                if cfg.mla is not None:
                    return A.MLACache(("batch", "seq", None), ("batch", "seq", None))
                kv = ("batch", "seq", "kv_heads", "head_dim")
                return A.KVCache(kv, kv)
            if kind == "rglru":
                return RG.RGLRUState(h=("batch", "rnn"), conv=("batch", None, "rnn"))
            if kind == "mlstm":
                return XL.MLSTMState(
                    c=("batch", "heads", None, "rnn"),
                    n=("batch", "heads", "rnn"),
                    m=("batch", "heads"),
                )
            if kind == "slstm":
                return XL.SLSTMState(
                    c=("batch", "rnn"), n=("batch", "rnn"),
                    m=("batch", "rnn"), h=("batch", "rnn"),
                )
            raise ValueError(kind)

        def stack(axes_tree):
            return jax.tree.map(
                lambda a: (None,) + a,
                axes_tree,
                is_leaf=lambda a: isinstance(a, tuple) and all(
                    isinstance(x, (str, type(None))) for x in a
                ),
            )

        return {
            "head": [block_axes(k) for k in self.head_kinds],
            "units": [stack(block_axes(k)) for k in self.unit_pattern],
            "tail": [block_axes(k) for k in self.tail_kinds],
        }

    def decode_step(
        self, params: dict, tokens: jax.Array, state: dict, pos: jax.Array
    ) -> tuple[jax.Array, dict]:
        """One decode step. tokens: (b, 1) (or (b, n) block); pos: scalar
        current cache length. Returns (logits for last position, new state)."""
        cfg, mctx = self.cfg, self.mctx
        x = embed_tokens(params["embed"], tokens, cfg)
        if tokens.shape[1] > 1:  # block prefill: same anchoring as forward
            x = mctx.constrain_batch(x)
        b, s = x.shape[0], x.shape[1]
        positions = pos + jnp.broadcast_to(jnp.arange(s), (b, s))
        new_state: dict = {"head": [], "tail": [], "units": []}

        for p, kind, moe, c in zip(params["head"], self.head_kinds, self.head_moe, state["head"]):
            x, nc, _ = _apply_block(p, x, cfg, kind, moe, mctx,
                                    positions=positions, cache=c, cache_pos=pos)
            new_state["head"].append(nc)

        if self.n_units:
            # The routed experts' stacks stay whole, indexed by layer inside
            # the MoE, so a decode token reads only the experts it reaches
            # (moe.moe_local); every other weight is scanned.
            units, experts = zip(*(_hold_out_experts(p, moe)
                                   for p, moe in zip(params["units"], self.unit_moe)))

            def unit_fn(carry, scanned):
                x = carry
                layer, unit_p, unit_c = scanned
                ncs = []
                for p, held, kind, moe, c in zip(unit_p, experts, self.unit_pattern,
                                                 self.unit_moe, unit_c):
                    if moe:
                        p = dict(p, moe=dict(p["moe"], **held))
                    x, nc, _ = _apply_block(p, x, cfg, kind, moe, mctx, positions=positions,
                                            cache=c, cache_pos=pos, layer=layer)
                    ncs.append(nc)
                return x, tuple(ncs)

            x, new_unit_state = jax.lax.scan(
                unit_fn, x, (jnp.arange(self.n_units), units, tuple(state["units"]))
            )
            new_state["units"] = list(new_unit_state)

        for p, kind, moe, c in zip(params["tail"], self.tail_kinds, self.tail_moe, state["tail"]):
            x, nc, _ = _apply_block(p, x, cfg, kind, moe, mctx,
                                    positions=positions, cache=c, cache_pos=pos)
            new_state["tail"].append(nc)

        x = apply_norm(params["final_norm"], x, cfg.norm)
        logits = lm_logits(params["embed"], x[:, -1:], cfg)
        return logits, new_state
