"""Multi-head attention: GQA, RoPE/M-RoPE, sliding window, KV cache;
and multi-head latent attention (MLA) over a latent cache.

The jnp reference path is what the distributed dry-run lowers (XLA SPMD
shards it); the Pallas flash kernel (repro.kernels.flash_attention) is the
TPU hot-path alternative, validated against this in tests and selectable
via ``use_flash``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .blocks import apply_mrope, apply_norm, apply_rope, truncated_normal, yarn_mscale

NEG_INF = -1e30


class KVCache(NamedTuple):
    k: jax.Array  # (batch, max_seq, n_kv_heads, head_dim)
    v: jax.Array


def init_attention(key, cfg, dtype) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    kq, kk, kv, ko = jax.random.split(key, 4)
    s = cfg.init_scale / np.sqrt(d)
    p = {
        "wq": truncated_normal(kq, (d, nq, hd), dtype, s),
        "wk": truncated_normal(kk, (d, nkv, hd), dtype, s),
        "wv": truncated_normal(kv, (d, nkv, hd), dtype, s),
        "wo": truncated_normal(ko, (nq, hd, d), dtype, cfg.init_scale / np.sqrt(nq * hd)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nq, hd), dtype)
        p["bk"] = jnp.zeros((nkv, hd), dtype)
        p["bv"] = jnp.zeros((nkv, hd), dtype)
    return p


def attention_axes(cfg) -> dict:
    p = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    if cfg.qkv_bias:
        p["bq"] = ("heads", "head_dim")
        p["bk"] = ("kv_heads", "head_dim")
        p["bv"] = ("kv_heads", "head_dim")
    return p


def _project_qkv(p: dict, x: jax.Array, cfg):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    return q, k, v


def _rope(q, k, positions, cfg):
    if cfg.rope == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    elif cfg.rope == "mrope":
        hd = cfg.resolved_head_dim // 2
        # Qwen2-VL-style section split over half-dim (t, h, w)
        sections = (hd - 2 * (hd // 4), hd // 4, hd // 4)
        pos3 = mrope_positions(positions, cfg)
        q = apply_mrope(q, pos3, sections, cfg.rope_theta)
        k = apply_mrope(k, pos3, sections, cfg.rope_theta)
    return q, k


def mrope_positions(positions: jax.Array, cfg) -> jax.Array:
    """(3, b, s) temporal/height/width positions. The leading
    ``n_frontend_tokens`` positions are image patches on a
    sqrt-grid (dynamic-resolution stub); the rest is text (t=h=w)."""
    n_img = cfg.n_frontend_tokens if cfg.frontend == "vision" else 0
    grid = max(int(np.sqrt(max(n_img, 1))), 1)
    is_img = positions < n_img
    h = jnp.where(is_img, (positions % (grid * grid)) // grid, positions)
    w = jnp.where(is_img, positions % grid, positions)
    t = jnp.where(is_img, 0, positions)
    return jnp.stack([t, h, w])


def sdpa(
    q: jax.Array,  # (b, sq, nq, hd)
    k: jax.Array,  # (b, skv, nkv, hd)
    v: jax.Array,
    *,
    causal: bool,
    window: int = 0,
    q_offset: jax.Array | int = 0,
    kv_len: jax.Array | None = None,
    k_positions: jax.Array | None = None,  # absolute key positions (ring cache)
    scale: float | None = None,  # None: 1 / sqrt(q's head dim)
) -> jax.Array:
    """Grouped-query SDPA with optional causal mask, sliding window and
    KV-cache length masking. fp32 softmax. The output's head dim is v's,
    which may differ from q's and k's."""
    b, sq, nq, hd = q.shape
    skv, nkv = k.shape[1], k.shape[2]
    groups = nq // nkv
    qg = q.reshape(b, sq, nkv, groups, hd)
    scores = jnp.einsum("bsngk,btnk->bngst", qg, k).astype(jnp.float32)
    scores = scores / np.sqrt(hd) if scale is None else scores * scale

    q_pos = jnp.arange(sq)[:, None] + q_offset  # absolute query positions
    k_pos = (k_positions if k_positions is not None else jnp.arange(skv))[None, :]
    mask = k_pos >= 0
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    if kv_len is not None:
        mask &= k_pos < kv_len
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bngst,btnk->bsngk", probs, v)
    return out.reshape(b, sq, nq, v.shape[-1])


def chunked_sdpa(
    q: jax.Array,  # (b, sq, nq, hd)
    k: jax.Array,  # (b, skv, nkv, hd)
    v: jax.Array,
    *,
    causal: bool,
    window: int = 0,
    q_offset: jax.Array | int = 0,
    kv_len: jax.Array | None = None,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    scale: float | None = None,  # None: 1 / sqrt(q's head dim)
) -> jax.Array:
    """Flash-style online-softmax attention in pure jnp (scan over q and kv
    chunks). Never materializes the (sq, skv) score matrix — this is what
    makes 32k prefill / 4k train lowerable at production batch sizes. The
    Pallas kernel (repro.kernels.flash_attention) is the TPU twin. The
    output's head dim is v's."""
    b, sq, nq, hd = q.shape
    skv, nkv = k.shape[1], k.shape[2]
    vd = v.shape[-1]
    g = nq // nkv
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    nq_chunks = (sq + q_chunk - 1) // q_chunk
    nkv_chunks = (skv + kv_chunk - 1) // kv_chunk
    pad_q = nq_chunks * q_chunk - sq
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    pad_kv = nkv_chunks * kv_chunk - skv
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    eff_kv_len = jnp.asarray(kv_len if kv_len is not None else skv)

    qg = q.reshape(b, nq_chunks, q_chunk, nkv, g, hd)
    qg = jnp.moveaxis(qg, 1, 0)  # (nQ, b, qc, nkv, g, hd)
    kc = jnp.moveaxis(k.reshape(b, nkv_chunks, kv_chunk, nkv, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, nkv_chunks, kv_chunk, nkv, vd), 1, 0)
    if scale is None:
        scale = 1.0 / np.sqrt(hd)

    def q_body(carry, inp):
        qi, q_blk = inp  # q_blk: (b, qc, nkv, g, hd)
        q_pos = qi * q_chunk + jnp.arange(q_chunk) + q_offset

        def kv_body(state, kv_inp):
            m, l_sum, acc = state
            kj, k_blk, v_blk = kv_inp
            k_pos = kj * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bqngk,btnk->bngqt", q_blk, k_blk).astype(jnp.float32) * scale
            mask = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                mask &= k_pos[None, :] <= q_pos[:, None]
            if window > 0:
                mask &= k_pos[None, :] > q_pos[:, None] - window
            mask &= k_pos[None, :] < eff_kv_len
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_sum_new = l_sum * corr + p.sum(-1)
            pv = jnp.einsum("bngqt,btnk->bngqk", p.astype(v_blk.dtype), v_blk)
            acc_new = acc * corr[..., None].astype(acc.dtype) + pv.astype(jnp.float32)
            return (m_new, l_sum_new, acc_new), None

        m0 = jnp.full((b, nkv, g, q_chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, nkv, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, nkv, g, q_chunk, vd), jnp.float32)
        (m, l_sum, acc), _ = jax.lax.scan(
            kv_body, (m0, l0, a0), (jnp.arange(nkv_chunks), kc, vc)
        )
        out = acc / jnp.maximum(l_sum, 1e-30)[..., None]
        out = jnp.moveaxis(out, 3, 1)  # (b, qc, nkv, g, hd)
        return carry, out.astype(q_blk.dtype)

    _, outs = jax.lax.scan(q_body, None, (jnp.arange(nq_chunks), qg))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, nq_chunks * q_chunk, nkv, g, vd)
    if pad_q:
        out = out[:, :sq]
    return out.reshape(b, sq, nq, vd)


# jnp attention dispatch: naive path keeps the oracle simple for short
# sequences; long sequences must never materialize (sq, skv).
CHUNKED_THRESHOLD = 2048


def dispatch_sdpa(q, k, v, *, q_chunk: int = 512, kv_chunk: int = 1024, **kw):
    sq, skv = q.shape[1], k.shape[1]
    if sq * skv > CHUNKED_THRESHOLD * CHUNKED_THRESHOLD or sq > CHUNKED_THRESHOLD:
        # q_chunk == 0: kv-only streaming (sequence-parallel plan — the
        # query seq axis may be mesh-sharded and must not be re-chunked)
        return chunked_sdpa(
            q, k, v, q_chunk=(q_chunk or sq), kv_chunk=kv_chunk, **kw
        )
    return sdpa(q, k, v, **kw)


def attend(
    p: dict,
    x: jax.Array,
    cfg,
    *,
    positions: jax.Array,
    cache: KVCache | None = None,
    cache_pos: jax.Array | int = 0,
) -> tuple[jax.Array, KVCache | None]:
    """Full attention sub-layer. With ``cache`` set, performs decode-style
    cache update (x is the new token block) and attends over the cache."""
    q, k, v = _project_qkv(p, x, cfg)
    q, k = _rope(q, k, positions, cfg)
    chunks = dict(q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk)
    if cache is None:
        out = dispatch_sdpa(q, k, v, causal=cfg.causal, window=cfg.window, **chunks)
        new_cache = None
    elif cfg.window > 0 and cache.k.shape[1] <= cfg.window:
        out, new_cache = _ring_attend(q, k, v, cache, cache_pos, cfg, chunks)
    else:
        ck = jax.lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype), (0, cache_pos, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype), (0, cache_pos, 0, 0))
        kv_len = cache_pos + x.shape[1]
        out = dispatch_sdpa(
            q, ck, cv,
            causal=cfg.causal, window=cfg.window,
            q_offset=cache_pos, kv_len=kv_len, **chunks,
        )
        new_cache = KVCache(ck, cv)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, new_cache


def _ring_attend(q, k, v, cache: KVCache, cache_pos, cfg, chunks):
    """Sliding-window ring-buffer KV cache (beyond-paper §Perf): the cache
    holds only the last `window` keys (exact — windowed attention never
    reads older ones). Slot for absolute position P is P % window; slot i
    currently holds position cache_len-1 - ((cache_len-1 - i) % window).

    Block prefill (s > 1) is supported at cache_pos == 0: in-block windowed
    attention + write the trailing `window` tokens into the ring."""
    w = cache.k.shape[1]
    s = q.shape[1]
    if s == 1:
        slot = cache_pos % w
        ck = jax.lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype), (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype), (0, slot, 0, 0))
        i = jnp.arange(w)
        k_positions = cache_pos - ((cache_pos - i) % w)  # absolute pos per slot
        out = sdpa(
            q, ck, cv, causal=cfg.causal, window=cfg.window,
            q_offset=cache_pos, k_positions=k_positions,
        )
        return out, KVCache(ck, cv)
    # block prefill
    out = dispatch_sdpa(q, k, v, causal=cfg.causal, window=cfg.window, **chunks)
    take = min(w, s)
    tail_k = k[:, s - take :].astype(cache.k.dtype)
    tail_v = v[:, s - take :].astype(cache.v.dtype)
    slots = (jnp.arange(s - take, s) % w)
    ck = cache.k.at[:, slots].set(tail_k)
    cv = cache.v.at[:, slots].set(tail_v)
    return out, KVCache(ck, cv)


def init_kv_cache(batch: int, max_seq: int, cfg, dtype=jnp.bfloat16) -> KVCache:
    ring = cfg.window > 0 and getattr(cfg, "ring_kv", True)
    seq = min(max_seq, cfg.window) if ring else max_seq
    shape = (batch, seq, cfg.n_kv_heads, cfg.resolved_head_dim)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2, no query compression)
# ---------------------------------------------------------------------------


class MLACache(NamedTuple):
    ckv: jax.Array  # (batch, max_seq, kv_lora_rank): the latent after its RMSNorm
    kpe: jax.Array  # (batch, max_seq, qk_rope_head_dim): the shared key, rotated


def init_mla(key, cfg, dtype) -> dict:
    a, d, h = cfg.mla, cfg.d_model, cfg.n_heads
    r = a.kv_lora_rank
    kq, ka, kb, ko = jax.random.split(key, 4)
    s = cfg.init_scale / np.sqrt(d)
    return {
        "wq": truncated_normal(kq, (d, h, a.qk_head_dim), dtype, s),
        "wkv_a": truncated_normal(ka, (d, r + a.qk_rope_head_dim), dtype, s),
        "kv_norm": {"scale": jnp.ones((r,), dtype)},
        "wkv_b": truncated_normal(
            kb, (r, h, a.qk_nope_head_dim + a.v_head_dim), dtype, cfg.init_scale / np.sqrt(r)
        ),
        "wo": truncated_normal(
            ko, (h, a.v_head_dim, d), dtype, cfg.init_scale / np.sqrt(h * a.v_head_dim)
        ),
    }


def mla_axes(cfg) -> dict:
    return {
        "wq": ("embed", "heads", "head_dim"),
        "wkv_a": ("embed", None),
        "kv_norm": {"scale": (None,)},
        "wkv_b": (None, "heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }


def mla_softmax_scale(cfg) -> float:
    """qk_head_dim^-1/2, times mscale(factor, mscale_all_dim)^2 under YaRN."""
    scale = cfg.mla.qk_head_dim**-0.5
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def _mla_absorbed(q_nope, q_pe, ckv, kpe, w_kv_b, nope: int, scale: float, kv_len):
    """Attention over the latent cache itself: W_UK folded into the query
    (q_nope W_UK^T is a query in latent space) and W_UV into the output
    (heads take their weighted latent, then W_UV), so no key or value is
    ever expanded. Queries are the last positions before ``kv_len``."""
    w_uk, w_uv = w_kv_b[..., :nope], w_kv_b[..., nope:]
    q_lat = jnp.einsum("bshk,chk->bshc", q_nope, w_uk)
    f32 = jnp.float32
    scores = jnp.einsum("bshc,btc->bhst", q_lat, ckv, preferred_element_type=f32)
    scores += jnp.einsum("bshr,btr->bhst", q_pe, kpe, preferred_element_type=f32)
    sq, skv = q_nope.shape[1], ckv.shape[1]
    q_pos = kv_len - sq + jnp.arange(sq)[:, None]
    mask = jnp.arange(skv)[None, :] <= q_pos
    scores = jnp.where(mask[None, None], scores * scale, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(ckv.dtype)
    o_lat = jnp.einsum("bhst,btc->bshc", probs, ckv)
    return jnp.einsum("bshc,chk->bshk", o_lat, w_uv)


def attend_mla(
    p: dict,
    x: jax.Array,
    cfg,
    *,
    positions: jax.Array,
    cache: MLACache | None = None,
    cache_pos: jax.Array | int = 0,
) -> tuple[jax.Array, MLACache | None]:
    """Latent attention sub-layer. q = x W_q (per head ``nope`` + ``rope``
    dims); [c_kv; k_pe] = x W_kv_a; c_kv passes an RMSNorm and k_pe the
    rotary embedding (one key for all heads); [k_nope; v] = c_kv W_kv_b.

    Without a cache, and for a block of several new tokens (prefill), keys
    and values are expanded and attended by ``dispatch_sdpa`` (the span
    ``mla.prefill``); a single new token attends over the latent cache with
    W_kv_b absorbed (``mla.decode``). Both write the same cache."""
    a = cfg.mla
    nope, r = a.qk_nope_head_dim, a.kv_lora_rank
    scale = mla_softmax_scale(cfg)
    s = x.shape[1]
    decode = cache is not None and s == 1
    with jax.named_scope("mla.decode" if decode else "mla.prefill"):
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        q_nope, q_pe = q[..., :nope], q[..., nope:]
        kv = x @ p["wkv_a"]
        ckv = apply_norm(p["kv_norm"], kv[..., :r], "rmsnorm")
        q_pe = apply_rope(q_pe, positions, cfg.rope_theta, cfg.rope_scaling)
        kpe = apply_rope(kv[..., None, r:], positions, cfg.rope_theta, cfg.rope_scaling)[:, :, 0]
        if cache is None:
            new_cache, kv_len, q_offset = None, None, 0
        else:
            new_cache = MLACache(*(
                jax.lax.dynamic_update_slice(old, new.astype(old.dtype), (0, cache_pos, 0))
                for old, new in ((cache.ckv, ckv), (cache.kpe, kpe))
            ))
            ckv, kpe = new_cache.ckv.astype(x.dtype), new_cache.kpe.astype(x.dtype)
            kv_len, q_offset = cache_pos + s, cache_pos
        if decode:
            out = _mla_absorbed(q_nope, q_pe, ckv, kpe, p["wkv_b"], nope, scale, kv_len)
        else:
            kvb = jnp.einsum("btc,chk->bthk", ckv, p["wkv_b"])
            k_nope, v = kvb[..., :nope], kvb[..., nope:]
            k_pe = jnp.broadcast_to(kpe[:, :, None], k_nope.shape[:3] + kpe.shape[-1:])
            out = dispatch_sdpa(
                jnp.concatenate([q_nope, q_pe], -1), jnp.concatenate([k_nope, k_pe], -1), v,
                causal=cfg.causal, q_offset=q_offset, kv_len=kv_len, scale=scale,
                q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk,
            )
        y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, new_cache


def init_mla_cache(batch: int, max_seq: int, cfg, dtype=jnp.bfloat16) -> MLACache:
    a = cfg.mla
    return MLACache(
        jnp.zeros((batch, max_seq, a.kv_lora_rank), dtype),
        jnp.zeros((batch, max_seq, a.qk_rope_head_dim), dtype),
    )
