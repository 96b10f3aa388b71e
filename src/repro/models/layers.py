"""Shared model layers: norms, RoPE, attention (GQA/MLA), FFN, MoE.

Functional style: params are nested dicts of arrays; every layer is
``fn(params, x, ...) -> y``.  Layer stacks carry a leading scan axis.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .sharding import ShardingRules, shard


def _init(key, shape, scale=None, dtype=jnp.float32):
    if scale is None:
        scale = 1.0 / (shape[0] ** 0.5)
    return (jax.random.truncated_normal(key, -2, 2, shape, jnp.float32)
            * scale).astype(dtype)


# ---------------------------------------------------------------- norms ----
def rms_norm(g, x, eps=1e-5):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * g


# ----------------------------------------------------------------- rope ----
def rope_freqs(head_dim: int, max_pos: int, theta: float = 10000.0):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_pos, dtype=jnp.float32)
    f = jnp.outer(t, inv)
    return jnp.cos(f), jnp.sin(f)


def apply_rope(x, pos):
    """x: (..., S, D); pos: (S,) or (B, S) int positions.  M-RoPE (qwen2-vl)
    degenerates to 1-D RoPE for the stubbed text-only backbone (DESIGN.md)."""
    d = x.shape[-1]
    inv = 1.0 / (10000.0 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = pos[..., :, None].astype(jnp.float32) * inv  # (..., S, D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    # broadcast over head axis: x (..., H, S, D) vs angles (..., S, D/2)
    if x.ndim == cos.ndim + 2:
        cos, sin = cos[..., None, :, :], sin[..., None, :, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# ----------------------------------------------------- chunked attention ----
def chunked_attention(q, k, v, *, causal=True, window=0, chunk=1024,
                      q_offset=0):
    """Online-softmax attention, scanning kv chunks — the XLA twin of the
    Pallas flash kernel (memory O(S·chunk) instead of O(S^2)).

    q: (B, H, Sq, D); k, v: (B, Hkv, Sk, D) with H % Hkv == 0.
    q_offset: absolute position of q[0] (decode/prefill continuation).
    """
    b, h, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    rep = h // hkv
    scale = 1.0 / (d ** 0.5)
    n_chunks = -(-sk // chunk)
    pad = n_chunks * chunk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kc = k.reshape(b, hkv, n_chunks, chunk, d).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(b, hkv, n_chunks, chunk, d).transpose(2, 0, 1, 3, 4)
    q32 = q.astype(jnp.float32)
    q_pos = q_offset + jnp.arange(sq)

    def step(carry, xs):
        m, l, acc = carry
        kb, vb, ci = xs
        kb = jnp.repeat(kb, rep, axis=1).astype(jnp.float32)
        vb = jnp.repeat(vb, rep, axis=1).astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, kb) * scale
        k_pos = ci * chunk + jnp.arange(chunk)
        mask = k_pos[None, :] < sk  # padding
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        if window > 0:
            mask = mask & ((q_pos[:, None] - k_pos[None, :]) < window)
        s = jnp.where(mask[None, None], s, -1e30)
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bhqk,bhkd->bhqd", p, vb)
        return (m_new, l, acc), None

    init = (jnp.full((b, h, sq, 1), -1e30, jnp.float32),
            jnp.zeros((b, h, sq, 1), jnp.float32),
            jnp.zeros((b, h, sq, d), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(
        step, init, (kc, vc, jnp.arange(n_chunks)))
    return (acc / jnp.where(l == 0, 1.0, l)).astype(q.dtype)


# ---------------------------------------------------------- GQA attention ----
def gqa_init(key, cfg, dtype):
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": _init(ks[0], (d, h * dh), dtype=dtype),
        "wk": _init(ks[1], (d, hkv * dh), dtype=dtype),
        "wv": _init(ks[2], (d, hkv * dh), dtype=dtype),
        "wo": _init(ks[3], (h * dh, d), dtype=dtype),
    }
    if cfg.attn_bias:
        p["bq"] = jnp.zeros((h * dh,), dtype)
        p["bk"] = jnp.zeros((hkv * dh,), dtype)
        p["bv"] = jnp.zeros((hkv * dh,), dtype)
    return p


def gqa_qkv(p, cfg, x, pos):
    b, s, _ = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["wq"] + (p.get("bq", 0))
    k = x @ p["wk"] + (p.get("bk", 0))
    v = x @ p["wv"] + (p.get("bv", 0))
    q = q.reshape(b, s, h, dh).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, hkv, dh).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, hkv, dh).transpose(0, 2, 1, 3)
    if cfg.rope != "none":
        q = apply_rope(q, pos)
        k = apply_rope(k, pos)
    return q, k, v


def decode_attention(q, k_cache, v_cache, n_valid):
    """Single-token attention over a (possibly ring-buffer) KV cache.

    q: (B,H,1,dh); caches: (B,Hkv,C,dh); n_valid: valid slot count (traced).
    RoPE is applied at absolute positions *before* caching, so slot order is
    irrelevant — only validity masking matters (layers.py ring-buffer note).
    """
    b, h, _, dh = q.shape
    hkv, c = k_cache.shape[1], k_cache.shape[2]
    rep = h // hkv
    kf = jnp.repeat(k_cache, rep, axis=1).astype(jnp.float32)
    vf = jnp.repeat(v_cache, rep, axis=1).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kf) / (dh ** 0.5)
    valid = jnp.arange(c)[None, None, None, :] < n_valid
    s = jnp.where(valid, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vf).astype(q.dtype)


def gqa_attention(p, cfg, x, *, pos, rules: Optional[ShardingRules],
                  cache=None, cache_len=None, window: int = 0):
    """Self-attention; with ``cache=(k_cache, v_cache)`` runs decode (x is
    the new token), returning (out, new_cache).  When ``window > 0`` the
    cache is a ring buffer of ``window`` slots (long_500k feasibility)."""
    b, s, _ = x.shape
    q, k, v = gqa_qkv(p, cfg, x, pos)
    if rules is not None:
        q = shard(q, rules.act_bhtd)
        if not rules.shard_heads:
            # anchor k/v too: stops GSPMD propagating head_dim shardings
            # from the column-sharded wk/wv into the attention dots
            k = shard(k, rules.act_bhtd)
            v = shard(v, rules.act_bhtd)
    if cache is not None:
        k_cache, v_cache = cache
        c = k_cache.shape[2]
        if s > 1:
            # batched prefill from an empty cache (cache_len == 0): attend
            # over the fresh keys, then fill the cache slab.  For ring
            # buffers (window) with s >= c, key at absolute position p
            # lands at slot p % c — a roll of the last c keys.
            out = chunked_attention(q, k, v, causal=True, window=window)
            if s >= c:
                k_cache = jnp.roll(k[:, :, -c:], s % c, axis=2).astype(
                    k_cache.dtype)
                v_cache = jnp.roll(v[:, :, -c:], s % c, axis=2).astype(
                    v_cache.dtype)
            else:
                k_cache = jax.lax.dynamic_update_slice_in_dim(
                    k_cache, k.astype(k_cache.dtype), cache_len, axis=2)
                v_cache = jax.lax.dynamic_update_slice_in_dim(
                    v_cache, v.astype(v_cache.dtype), cache_len, axis=2)
        else:
            slot = cache_len % c if window > 0 else cache_len
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                k_cache, k.astype(k_cache.dtype), slot, axis=2)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                v_cache, v.astype(v_cache.dtype), slot, axis=2)
            n_valid = jnp.minimum(cache_len + 1, c)
            out = decode_attention(q, k_cache, v_cache, n_valid)
        new_cache = (k_cache, v_cache)
    else:
        out = chunked_attention(q, k, v, causal=not cfg.is_encoder,
                                window=window)
        new_cache = None
    out = out.transpose(0, 2, 1, 3).reshape(b, s, -1)
    out = out @ p["wo"]
    return out, new_cache


# ------------------------------------------------------------- MLA (MiniCPM3)
def mla_init(key, cfg, dtype):
    d, h, dh, r = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.mla_kv_rank
    ks = jax.random.split(key, 5)
    return {
        "wq": _init(ks[0], (d, h * dh), dtype=dtype),
        "w_dkv": _init(ks[1], (d, r), dtype=dtype),      # latent down-proj
        "w_uk": _init(ks[2], (r, h * dh), dtype=dtype),  # latent -> K
        "w_uv": _init(ks[3], (r, h * dh), dtype=dtype),  # latent -> V
        "wo": _init(ks[4], (h * dh, d), dtype=dtype),
    }


def mla_attention(p, cfg, x, *, pos, rules, cache=None, cache_len=None):
    """Multi-head latent attention: the KV cache stores the rank-r latent
    (the paper-style fused chain ``D = softmax(Q(K)ᵀ)·(latent·W_uv)`` keeps
    the expanded K/V as tile-local intermediates)."""
    b, s, _ = x.shape
    h, dh, r = cfg.n_heads, cfg.head_dim, cfg.mla_kv_rank
    q = (x @ p["wq"]).reshape(b, s, h, dh).transpose(0, 2, 1, 3)
    lat = x @ p["w_dkv"]                                   # (b, s, r)
    if cfg.rope != "none":
        q = apply_rope(q, pos)
    if cache is not None:
        lat_cache = jax.lax.dynamic_update_slice_in_dim(
            cache, lat.astype(cache.dtype), cache_len, axis=1)
        if s > 1:   # batched prefill (cache_len == 0)
            k = (lat @ p["w_uk"]).reshape(b, s, h, dh).transpose(0, 2, 1, 3)
            v = (lat @ p["w_uv"]).reshape(b, s, h, dh).transpose(0, 2, 1, 3)
            if cfg.rope != "none":
                k = apply_rope(k, pos)
            out = chunked_attention(q, k, v, causal=True)
            return (out.transpose(0, 2, 1, 3).reshape(b, s, -1) @ p["wo"],
                    lat_cache)
        sk = lat_cache.shape[1]
        k = (lat_cache @ p["w_uk"]).reshape(b, sk, h, dh).transpose(0, 2, 1, 3)
        v = (lat_cache @ p["w_uv"]).reshape(b, sk, h, dh).transpose(0, 2, 1, 3)
        if cfg.rope != "none":
            k = apply_rope(k, jnp.arange(sk))
        out = decode_attention(q, k, v, jnp.minimum(cache_len + 1, sk))
    else:
        lat_cache = None
        k = (lat @ p["w_uk"]).reshape(b, s, h, dh).transpose(0, 2, 1, 3)
        v = (lat @ p["w_uv"]).reshape(b, s, h, dh).transpose(0, 2, 1, 3)
        if cfg.rope != "none":
            k = apply_rope(k, pos)
        out = chunked_attention(q, k, v, causal=True)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, -1) @ p["wo"]
    return out, lat_cache


# ------------------------------------------------------- cross-attention ----
def cross_attention(p, cfg, x, enc_out, *, rules):
    b, s, _ = x.shape
    h, dh = cfg.n_heads, cfg.head_dim
    se = enc_out.shape[1]
    q = (x @ p["wq"]).reshape(b, s, h, dh).transpose(0, 2, 1, 3)
    k = (enc_out @ p["wk"]).reshape(b, se, -1, dh).transpose(0, 2, 1, 3)
    v = (enc_out @ p["wv"]).reshape(b, se, -1, dh).transpose(0, 2, 1, 3)
    out = chunked_attention(q, k, v, causal=False)
    return out.transpose(0, 2, 1, 3).reshape(b, s, -1) @ p["wo"], None


# ------------------------------------------------------------------- FFN ----
def ffn_init(key, cfg, dtype):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w_gate": _init(ks[0], (d, f), dtype=dtype),
        "w_up": _init(ks[1], (d, f), dtype=dtype),
        "w_down": _init(ks[2], (f, d), dtype=dtype),
    }


def ffn_apply(p, cfg, x, rules: Optional[ShardingRules]):
    """Gated FFN (SwiGLU/GeGLU).  This is the dense limiting case of tile
    fusion — on TPU it lowers to kernels/fused_ffn keeping h in VMEM."""
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    h = act(x @ p["w_gate"]) * (x @ p["w_up"])
    if rules is not None:
        h = shard(h, rules.act_btf)
    return h @ p["w_down"]


# ------------------------------------------------------------------- MoE ----
def moe_init(key, cfg, dtype):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    p = {
        "router": _init(ks[0], (d, e), scale=0.02, dtype=jnp.float32),
        "w1": _init(ks[1], (e, d, f), dtype=dtype),       # gate proj
        "w3": _init(ks[2], (e, d, f), dtype=dtype),       # up proj
        "w2": _init(ks[3], (e, f, d), dtype=dtype),       # down proj
    }
    if cfg.moe_shared_expert:
        p["shared"] = ffn_init(jax.random.fold_in(key, 7), cfg, dtype)
    return p


def _row_dispatch(cfg, xf, router, cap):
    """Capacity dispatch for ONE token row (s, d) -> (xe, combine-aux).

    All sort/gather/scatter indices stay within the row — local to whatever
    shard holds the row."""
    s, d = xf.shape
    e, k = cfg.n_experts, cfg.moe_top_k
    logits = xf.astype(jnp.float32) @ router               # (s, e)
    gates = jax.nn.softmax(logits, axis=-1)
    top_g, top_e = jax.lax.top_k(gates, k)                 # (s, k)
    top_g = top_g / jnp.clip(top_g.sum(-1, keepdims=True), 1e-9)

    flat_e = top_e.reshape(-1)                             # (s*k,)
    flat_t = jnp.repeat(jnp.arange(s), k)
    flat_g = top_g.reshape(-1)
    order = jnp.argsort(flat_e)
    se_, st_, sg_ = flat_e[order], flat_t[order], flat_g[order]
    pos_in_e = jnp.arange(se_.shape[0]) - jnp.searchsorted(
        se_, jnp.arange(e))[se_]
    keep = pos_in_e < cap
    slot = jnp.where(keep, se_ * cap + pos_in_e, e * cap)  # overflow -> drop
    gathered = jnp.zeros((e * cap + 1, d), xf.dtype).at[slot].set(xf[st_])
    xe = gathered[:-1].reshape(e, cap, d)
    return xe, (keep, slot, st_, sg_)


def _row_combine(ye, aux, s, d, dtype):
    keep, slot, st_, sg_ = aux
    e_cap = ye.shape[0] * ye.shape[1]
    yf = ye.reshape(e_cap, d)
    contrib = jnp.where(keep[:, None], yf[jnp.clip(slot, 0, e_cap - 1)]
                        * sg_[:, None].astype(dtype), 0)
    return jnp.zeros((s, d), dtype).at[st_].add(contrib)


def _expert_ffn(cfg, xe, w1, w3, w2):
    """The fused two-matmul expert chain (tile fusion's dense instance —
    kernels/moe.py on TPU keeps h in VMEM)."""
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    h = act(jnp.einsum("ecd,edf->ecf", xe, w1)) * \
        jnp.einsum("ecd,edf->ecf", xe, w3)
    return jnp.einsum("ecf,efd->ecd", h, w2)


def moe_apply(p, cfg, x, rules: Optional[ShardingRules],
              capacity_factor: float = 1.25):
    """Top-k MoE: capacity-based sorted dispatch per batch row.

    Tile-fusion mapping (DESIGN.md §4): the dispatch one-hot is the sparse A;
    tokens of one expert form a fused tile; gather (wavefront-0 producer) →
    two expert matmuls with the intermediate kept local → scatter (the single
    barrier).

    §Perf iterations 1+3 (beyond-paper): dispatch is per batch row (a global
    argsort over the data-sharded token axis lowered to TB-scale
    collectives), and under a mesh the whole layer runs in shard_map —
    dispatch scatter/gather stay device-local (GSPMD all-gathered the
    (b, e·cap, d) scatter operand otherwise) and the expert contraction is
    Megatron-style f-sharded with ONE psum of (b_local, s, d) per layer.
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.moe_top_k
    cap = int(capacity_factor * s * k / e)
    cap = max(8, -(-cap // 8) * 8)

    def local_moe(router, w1, w3, w2, shared, xl):
        def row(xf):
            xe, aux = _row_dispatch(cfg, xf, router, cap)
            ye = _expert_ffn(cfg, xe, w1, w3, w2)   # f-sliced under shard_map
            return _row_combine(ye, aux, s, d, xl.dtype)
        y = jax.vmap(row)(xl)
        if shared is not None:
            act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
            h = act(xl @ shared["w_gate"]) * (xl @ shared["w_up"])
            y = y + h @ shared["w_down"]
        return y

    shared = p.get("shared")
    n_batch_shards = 1
    if rules is not None and rules.mesh is not None:
        sizes = dict(zip(rules.mesh.axis_names, rules.mesh.devices.shape))
        for ax in rules.batch_axes:
            n_batch_shards *= sizes.get(ax, 1)
    if rules is None or rules.mesh is None or b % n_batch_shards != 0:
        # single-device path, or batch (e.g. long_500k b=1) not divisible by
        # the data axes — tiny dispatch, GSPMD handles it
        return local_moe(p["router"], p["w1"], p["w3"], p["w2"], shared, x)

    from jax.sharding import PartitionSpec as P
    ba, mx = rules.batch_axes, rules.model_axis
    shared_spec = None if shared is None else {
        "w_gate": P(None, mx), "w_up": P(None, mx), "w_down": P(mx, None)}
    f = jax.shard_map(
        lambda router, w1, w3, w2, sh, xl: jax.lax.psum(
            local_moe(router, w1, w3, w2, sh, xl), mx),
        mesh=rules.mesh,
        in_specs=(P(), P(None, None, mx), P(None, None, mx),
                  P(None, mx, None), shared_spec, P(ba, None, None)),
        out_specs=P(ba, None, None),
        check_vma=False,
    )
    return f(p["router"], p["w1"], p["w3"], p["w2"], shared, x)


# ------------------------------------------------------------- embedding ----
def embed_init(key, cfg, dtype):
    ks = jax.random.split(key, 2)
    return {
        "embed": _init(ks[0], (cfg.vocab_size, cfg.d_model), scale=0.02,
                       dtype=dtype),
        "lm_head": _init(ks[1], (cfg.d_model, cfg.vocab_size), dtype=dtype),
    }
