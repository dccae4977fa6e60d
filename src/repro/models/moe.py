"""Fine-grained Mixture-of-Experts (DeepSeekMoE / Kimi-K2 style).

Routed experts + optional shared experts. Two execution paths:

* **local** — single-device/CPU smoke path: sort tokens by expert, one
  ragged (grouped) GEMM per projection (``jax.lax.ragged_dot``).
* **expert-parallel (EP)** — production path inside ``jax.shard_map``:
  experts are sharded over the ``model`` mesh axis; each data shard routes
  its tokens, packs capacity-bounded per-owner send buffers, exchanges them
  with ``all_to_all``, runs the ragged expert GEMMs on its expert slice,
  and reverses the exchange before the weighted combine. Token dropping
  beyond capacity follows standard practice (GShard/Switch); dropped slots
  are masked out of the combine. Shared experts run as a plain dense GLU
  outside the shard_map (tensor-parallel via pjit like any MLP).

The routed output is replicated over the model axis by construction (every
model rank sends identical buffers), so ``check_vma=False`` is used and the
combine result carries data-parallel sharding only.

A layer may hold a share of the routed experts (``MoEConfig.held`` from
``first_held``), as one chip of an expert-parallel deployment does: the
router scores all ``n_experts`` with the published top-k, and the local
path computes exactly the part of the output that the held experts give
(no capacity, nothing dropped) plus the shared experts; the absent
experts' part is left out. Both paths compute their experts' part with
:func:`_expert_ffn`. Spans: ``moe.route``, ``moe.experts``, ``moe.shared``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .blocks import truncated_normal

# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def init_moe(key, cfg, dtype) -> dict:
    m = cfg.moe
    d, f = cfg.d_model, m.d_expert
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    s = cfg.init_scale / np.sqrt(d)
    p = {
        "router": truncated_normal(kr, (d, m.n_experts), jnp.float32, s),
        "w_gate": truncated_normal(kg, (m.n_held, d, f), dtype, s),
        "w_up": truncated_normal(ku, (m.n_held, d, f), dtype, s),
        "w_down": truncated_normal(kd, (m.n_held, f, d), dtype, cfg.init_scale / np.sqrt(f)),
    }
    if m.n_shared:
        ks1, ks2, ks3 = jax.random.split(ks, 3)
        fs = m.n_shared * f
        p["shared"] = {
            "gate": truncated_normal(ks1, (d, fs), dtype, s),
            "up": truncated_normal(ks2, (d, fs), dtype, s),
            "down": truncated_normal(ks3, (fs, d), dtype, cfg.init_scale / np.sqrt(fs)),
        }
    return p


def moe_axes(cfg) -> dict:
    p = {
        "router": ("embed", None),
        "w_gate": ("experts", "embed", "expert_ff"),
        "w_up": ("experts", "embed", "expert_ff"),
        "w_down": ("experts", "expert_ff", "embed"),
    }
    if cfg.moe.n_shared:
        p["shared"] = {"gate": ("embed", "mlp"), "up": ("embed", "mlp"), "down": ("mlp", "embed")}
    return p


# ---------------------------------------------------------------------------
# Routing
# ---------------------------------------------------------------------------


def _route(xf: jax.Array, router: jax.Array, m) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k routing over all ``m.n_experts``: a softmax, then greedy top-k,
    renormalised to sum to 1 where ``m.norm_topk_prob`` (DeepSeekMoE) and
    times ``m.routed_scaling_factor``. Returns (expert_ids (N,k), probs
    (N,k), aux_loss)."""
    logits = (xf.astype(jnp.float32) @ router).astype(jnp.float32)  # (N, E)
    probs_full = jax.nn.softmax(logits, axis=-1)
    probs, ids = jax.lax.top_k(probs_full, m.top_k)
    if m.norm_topk_prob:
        probs = probs / jnp.maximum(probs.sum(-1, keepdims=True), 1e-9)
    probs = probs * m.routed_scaling_factor
    # Switch/GShard load-balance aux: E * sum_e f_e * P_e
    pe = probs_full.mean(axis=0)
    fe = jnp.zeros((m.n_experts,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    fe = fe / jnp.maximum(fe.sum(), 1.0)
    aux = m.n_experts * jnp.sum(fe * pe)
    return ids, probs.astype(xf.dtype), aux


def _expert_ffn(tokens: jax.Array, eids: jax.Array, p: dict, n_experts: int,
                impl: str = "ragged", capacity_factor: float = 1.5) -> jax.Array:
    """Grouped expert GLU-FFN over tokens labelled by ``eids``.

    ``eids == n_experts`` marks invalid/padding rows (zero output).

    impl="ragged": ``jax.lax.ragged_dot`` x3. Semantically exact (no
    second-level dropping) but XLA's dense lowering multiplies FLOPs by
    the local expert count — fine on backends with native grouped GEMM.

    impl="batched": capacity-bounded scatter into an (E, cap, d) buffer +
    three *batched* dense GEMMs. This is the MXU-shaped form: compiled
    FLOPs = active-expert FLOPs x capacity_factor (EXPERIMENTS.md §Perf,
    kimi-k2 iteration). Tokens beyond per-expert capacity are dropped
    (standard GShard/Switch semantics)."""
    m, d = tokens.shape
    if impl == "ragged":
        safe_eids = jnp.minimum(eids, n_experts - 1)  # trash rows are zero tokens
        order = jnp.argsort(safe_eids)
        sorted_tok = tokens[order]
        group_sizes = jnp.bincount(safe_eids, length=n_experts).astype(jnp.int32)
        gate = jax.lax.ragged_dot(sorted_tok, p["w_gate"], group_sizes)
        up = jax.lax.ragged_dot(sorted_tok, p["w_up"], group_sizes)
        h = (jax.nn.silu(gate.astype(jnp.float32)).astype(tokens.dtype)) * up.astype(tokens.dtype)
        out = jax.lax.ragged_dot(h, p["w_down"], group_sizes).astype(tokens.dtype)
        return jnp.zeros_like(out).at[order].set(out)  # unsort

    assert impl == "batched", impl
    cap = max(int(np.ceil(m / n_experts * capacity_factor)), 1)
    order = jnp.argsort(eids)
    eid_s = eids[order]
    counts = jnp.bincount(eid_s, length=n_experts + 1)
    starts = jnp.concatenate([jnp.zeros(1, counts.dtype), jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(m) - starts[eid_s]
    valid = (pos < cap) & (eid_s < n_experts)
    buf = jnp.zeros((n_experts + 1, cap, d), tokens.dtype).at[
        jnp.where(valid, eid_s, n_experts), pos
    ].set(tokens[order], mode="drop")
    buf = buf[:n_experts]
    gate = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    up = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    # (kimi §Perf iteration 3 tried bf16 GLU here — refuted: the dominant
    # converts are the attention chunk accumulators, not this path)
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(tokens.dtype) * up.astype(tokens.dtype)
    out = jnp.einsum("ecf,efd->ecd", h, p["w_down"]).astype(tokens.dtype)
    gathered = out[jnp.minimum(eid_s, n_experts - 1), jnp.minimum(pos, cap - 1)]
    gathered = jnp.where(valid[:, None], gathered, 0)
    return jnp.zeros_like(tokens).at[order].set(gathered)


# ---------------------------------------------------------------------------
# Local path
# ---------------------------------------------------------------------------


def moe_local(p: dict, x: jax.Array, cfg) -> tuple[jax.Array, jax.Array]:
    """Single-shard routed-experts forward of the held experts. x: (b, s, d).

    Routes over all ``n_experts``; an assignment to an expert this layer
    does not hold becomes a zero token under the trash id ``n_held`` and
    weighs nothing in the combine."""
    m = cfg.moe
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    with jax.named_scope("moe.route"):
        ids, probs, aux = _route(xf, p["router"], m)
    with jax.named_scope("moe.experts"):
        n, k = ids.shape
        tok_idx = jnp.repeat(jnp.arange(n), k)
        local = ids.reshape(-1) - m.first_held
        held = (local >= 0) & (local < m.n_held)
        out_flat = _expert_ffn(
            jnp.where(held[:, None], xf[tok_idx], 0), jnp.where(held, local, m.n_held), p, m.n_held,
            impl=getattr(m, "expert_impl", "ragged"),
            capacity_factor=m.capacity_factor + 0.25,
        )
        w = jnp.where(held, probs.reshape(-1), 0)
        y = jnp.zeros_like(xf).at[tok_idx].add(out_flat * w[:, None])
    return y.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# Expert-parallel path (shard_map body)
# ---------------------------------------------------------------------------


def _moe_ep_body(p: dict, x: jax.Array, cfg, model_axis: str, data_axes: tuple[str, ...]):
    """Per-device body under shard_map. x: (b_loc, s, d); expert weights are
    the local expert slice (E_loc, ...)."""
    m = cfg.moe
    n_shards = jax.lax.axis_size(model_axis)
    e_loc = m.n_experts // n_shards
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    n = xf.shape[0]

    with jax.named_scope("moe.route"):
        ids, probs, aux = _route(xf, p["router"], m)
    flat_ids = ids.reshape(-1)  # (n*k,)
    tok_idx = jnp.repeat(jnp.arange(n), m.top_k)
    owner = flat_ids // e_loc

    cap = int(np.ceil(n * m.top_k / n_shards * m.capacity_factor))
    # sort assignments by owner; position within owner group
    order = jnp.argsort(owner)
    owner_s = owner[order]
    counts = jnp.bincount(owner_s, length=n_shards)
    starts = jnp.concatenate([jnp.zeros(1, counts.dtype), jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(n * m.top_k) - starts[owner_s]
    valid = pos < cap
    # capacity-bounded scatter into per-owner send buffers (drop overflow)
    row = jnp.where(valid, owner_s, n_shards)  # out-of-range -> dropped
    send_tok = jnp.zeros((n_shards, cap, d), x.dtype).at[row, pos].set(
        xf[tok_idx[order]], mode="drop"
    )
    # unwritten (padding) slots carry the trash expert id e_loc so the
    # batched expert impl never charges them against a real expert's capacity
    send_eid = jnp.full((n_shards, cap), e_loc, jnp.int32).at[row, pos].set(
        (flat_ids[order] % e_loc).astype(jnp.int32), mode="drop"
    )

    # exchange: recv[j] = what peer j sent to me
    recv_tok = jax.lax.all_to_all(send_tok, model_axis, split_axis=0, concat_axis=0, tiled=False)
    recv_eid = jax.lax.all_to_all(send_eid, model_axis, split_axis=0, concat_axis=0, tiled=False)

    # local expert compute (dropped slots are zero tokens -> zero outputs)
    with jax.named_scope("moe.experts"):
        out = _expert_ffn(
            recv_tok.reshape(-1, d), recv_eid.reshape(-1), p, e_loc,
            impl=getattr(m, "expert_impl", "ragged"),
            capacity_factor=m.capacity_factor + 0.25,
        )
    out = out.reshape(n_shards, cap, d)

    # reverse exchange and weighted combine
    back = jax.lax.all_to_all(out, model_axis, split_axis=0, concat_axis=0, tiled=False)
    w = jnp.where(valid, probs.reshape(-1)[order], 0).astype(x.dtype)
    gathered = back[jnp.clip(row, 0, n_shards - 1), pos]  # (n*k, d)
    y = jnp.zeros_like(xf).at[tok_idx[order]].add(gathered * w[:, None])

    aux = jax.lax.pmean(aux, data_axes) if data_axes else aux
    return y.reshape(b, s, d), aux


def moe_ep(p: dict, x: jax.Array, cfg, mesh, data_axes: tuple[str, ...], model_axis: str):
    """shard_map-wrapped expert-parallel MoE."""
    from jax.sharding import PartitionSpec as P

    dp = tuple(data_axes)
    body = partial(_moe_ep_body, cfg=cfg, model_axis=model_axis, data_axes=dp)
    param_specs = {
        "router": P(None, None),
        "w_gate": P(model_axis, None, None),
        "w_up": P(model_axis, None, None),
        "w_down": P(model_axis, None, None),
    }
    pp = {k: p[k] for k in param_specs}
    return jax.shard_map(
        body,
        mesh=mesh,
        check_vma=False,
        in_specs=(param_specs, P(dp, None, None)),
        out_specs=(P(dp, None, None), P()),
    )(pp, x)


# ---------------------------------------------------------------------------
# Full MoE layer (shared + routed)
# ---------------------------------------------------------------------------


def apply_moe(
    p: dict, x: jax.Array, cfg, mesh=None, data_axes: tuple[str, ...] = (), model_axis: str = ""
) -> tuple[jax.Array, jax.Array]:
    m = cfg.moe
    use_ep = (
        mesh is not None
        and model_axis
        and mesh.shape[model_axis] > 1
        and m.n_held == m.n_experts
        and m.n_experts % mesh.shape[model_axis] == 0
    )
    if use_ep:
        y, aux = moe_ep(p, x, cfg, mesh, data_axes, model_axis)
    else:
        y, aux = moe_local(p, x, cfg)
    if m.n_shared:
        with jax.named_scope("moe.shared"):
            sp = p["shared"]
            h = jax.nn.silu((x @ sp["gate"]).astype(jnp.float32)).astype(x.dtype) * (x @ sp["up"])
            y = y + h @ sp["down"]
    return y, aux
