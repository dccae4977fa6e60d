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
:func:`_expert_ffn`, except where a local layer has no more tokens than
held experts (decode): it then reads only the held experts its tokens
reach (:func:`_reached_experts`). Spans: ``moe.route``, ``moe.experts``,
``moe.experts.reached``, ``moe.shared``; :func:`tally_paths` counts the
path each traced lowering takes.
"""

from __future__ import annotations

import collections
import contextlib
import threading
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .blocks import truncated_normal

# The routed experts' stacked weights: (n_held, ...) in a layer's params,
# (layers, n_held, ...) where a layer scan leaves them whole (``layer=``).
EXPERT_KEYS = ("w_gate", "w_up", "w_down")

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


class _PathTally(threading.local):
    def __init__(self):
        self.open: list[collections.Counter] = []


_tally = _PathTally()


@contextlib.contextmanager
def tally_paths():
    """Yields a Counter that counts, while open, each expert lowering
    traced on this thread by the path it takes: ``reached``
    (:func:`_reached_experts`) or ``_expert_ffn``'s ``impl`` (``ragged``,
    ``batched``). A layer scan traces its body once, so it counts once
    for all the layers it runs."""
    counter: collections.Counter = collections.Counter()
    _tally.open.append(counter)
    try:
        yield counter
    finally:
        _tally.open.remove(counter)


def _took(path: str) -> None:
    for counter in _tally.open:
        counter[path] += 1


def _expert_ffn(tokens: jax.Array, eids: jax.Array, p: dict, n_experts: int,
                impl: str = "ragged", capacity_factor: float = 1.5) -> jax.Array:
    """Grouped expert GLU-FFN over tokens labelled by ``eids``.

    ``eids == n_experts`` marks invalid/padding rows (zero output).

    impl="ragged": ``jax.lax.ragged_dot`` x3. Semantically exact (no
    second-level dropping). It reads every expert of ``p`` whole, whatever
    the group sizes (TPU's lowering does, and XLA's dense lowering also
    multiplies FLOPs by the expert count): the grouped GEMM for many
    tokens, and the reason decode takes :func:`_reached_experts`.

    impl="batched": capacity-bounded scatter into an (E, cap, d) buffer +
    three *batched* dense GEMMs. This is the MXU-shaped form: compiled
    FLOPs = active-expert FLOPs x capacity_factor. Tokens beyond
    per-expert capacity are dropped (standard GShard/Switch semantics)."""
    _took(impl)
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
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(tokens.dtype) * up.astype(tokens.dtype)
    out = jnp.einsum("ecf,efd->ecd", h, p["w_down"]).astype(tokens.dtype)
    gathered = out[jnp.minimum(eid_s, n_experts - 1), jnp.minimum(pos, cap - 1)]
    gathered = jnp.where(valid[:, None], gathered, 0)
    return jnp.zeros_like(tokens).at[order].set(gathered)


# ---------------------------------------------------------------------------
# Local path
# ---------------------------------------------------------------------------


def _at_layer(p: dict, layer) -> dict:
    """``p`` with its routed experts' stacks cut to layer ``layer`` (None:
    they are one layer's already)."""
    if layer is None:
        return p
    return dict(p, **{k: p[k][layer] for k in EXPERT_KEYS})


def _grouped_experts(xf: jax.Array, ids: jax.Array, probs: jax.Array, p: dict, m) -> jax.Array:
    """The held experts' part of each token's output through
    :func:`_expert_ffn` (``m.expert_impl``, ``ragged`` by default). xf:
    (n, d); ids, probs: (n, k). An assignment to an expert not held
    becomes a zero token under the trash id ``n_held`` and weighs nothing
    in the combine."""
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
    return jnp.zeros_like(xf).at[tok_idx].add(out_flat * w[:, None])


def _reached_experts(xf: jax.Array, ids: jax.Array, probs: jax.Array, p: dict, m,
                     layer=None) -> jax.Array:
    """What :func:`_grouped_experts` gives, reading only the held experts
    that the tokens reach. xf: (n, d); ids, probs: (n, k).

    A ``while_loop`` runs once for each distinct held expert reached (c of
    them, at most min(n * k, n_held)), in the order the assignments first
    reach them: at n = 1 that is the token's top-k order, the order of the
    grouped path's combine. Each iteration takes the expert's three
    matrices by a dynamic index; with ``layer`` (the stacks left whole by a
    layer scan) the index reaches into the stack, so the program reads c
    experts, and no layer's n_held. It computes the expert's GLU for all n
    rows (SiLU in float32, then ``xf``'s dtype, as ``_expert_ffn``) and
    adds ``prob * out``, in ``xf``'s dtype, to the rows that route to it.
    The loop's trip count is data, so reverse-mode differentiation does
    not go through it: the path is for inference."""
    n, k = ids.shape
    local = ids.reshape(-1) - m.first_held
    slot = jnp.where((local >= 0) & (local < m.n_held), local, m.n_held)  # (n * k,)
    first = jnp.full((m.n_held + 1,), n * k).at[slot].min(jnp.arange(n * k))[: m.n_held]
    order = jnp.argsort(first)  # reached experts first, unreached (first = n * k) last
    slot = slot.reshape(n, k)

    def body(j, y):
        e = order[j]
        idx = e if layer is None else (layer, e)
        w_gate, w_up, w_down = (p[key][idx] for key in EXPERT_KEYS)
        gate, up = xf @ w_gate, xf @ w_up
        h = jax.nn.silu(gate.astype(jnp.float32)).astype(xf.dtype) * up.astype(xf.dtype)
        out = (h @ w_down).astype(xf.dtype)
        sel = slot == e  # (n, k): a token's k experts differ, so one True at most
        w = jnp.where(sel, probs, 0).sum(-1, keepdims=True)
        return y + jnp.where(sel.any(-1, keepdims=True), out * w, 0)

    return jax.lax.fori_loop(0, (first < n * k).sum(), body, jnp.zeros_like(xf))


def moe_local(p: dict, x: jax.Array, cfg, layer=None) -> tuple[jax.Array, jax.Array]:
    """Single-shard routed-experts forward of the held experts. x: (b, s, d).

    Routes over all ``n_experts``; an assignment to an expert this layer
    does not hold weighs nothing in the combine. ``layer``: where ``p``'s
    routed experts are the whole stacks of a layer scan, this layer's
    index in them.

    The path follows the token count n = b * s, by the bytes each reads.
    ``ragged`` reads all n_held experts whole (and a layer scan's slice of
    them cannot fuse into its lowering, so XLA copies it first);
    :func:`_reached_experts` reads the c distinct held experts reached, at
    most min(n * k, n_held), and computes each for all n rows. With n <=
    n_held it takes the reached path: batch-1 decode of a top-6 of 64
    router reaches 6 * n_held / 64 held experts on average. Past n_held
    tokens nearly every held expert is reached (n_held * (1 - (1 -
    k / n_experts)^n) on average), the reads are the same, and n rows per
    expert cost more than the grouped GEMM's n * k rows in all, so prefill
    and training take :func:`_grouped_experts`."""
    m = cfg.moe
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    with jax.named_scope("moe.route"):
        ids, probs, aux = _route(xf, p["router"], m)
    if xf.shape[0] <= m.n_held:
        _took("reached")
        with jax.named_scope("moe.experts.reached"):
            y = _reached_experts(xf, ids, probs, p, m, layer)
    else:
        with jax.named_scope("moe.experts"):
            y = _grouped_experts(xf, ids, probs, _at_layer(p, layer), m)
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
    p: dict, x: jax.Array, cfg, mesh=None, data_axes: tuple[str, ...] = (), model_axis: str = "",
    layer=None,
) -> tuple[jax.Array, jax.Array]:
    """Shared plus routed experts. ``layer``: where ``p``'s routed experts
    are the whole stacks of a layer scan, this layer's index in them."""
    m = cfg.moe
    use_ep = (
        mesh is not None
        and model_axis
        and mesh.shape[model_axis] > 1
        and m.n_held == m.n_experts
        and m.n_experts % mesh.shape[model_axis] == 0
    )
    if use_ep:
        y, aux = moe_ep(_at_layer(p, layer), x, cfg, mesh, data_axes, model_axis)
    else:
        y, aux = moe_local(p, x, cfg, layer)
    if m.n_shared:
        with jax.named_scope("moe.shared"):
            sp = p["shared"]
            h = jax.nn.silu((x @ sp["gate"]).astype(jnp.float32)).astype(x.dtype) * (x @ sp["up"])
            y = y + h @ sp["down"]
    return y, aux
