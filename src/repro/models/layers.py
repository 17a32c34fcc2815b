"""Core neural layers shared by all architectures (pure-JAX, pytree params).

Everything here is a pure function: ``init_*`` builds a param pytree,
``*_apply`` consumes it. No framework dependency (flax/optax absent in this
container by design) — params are plain nested dicts of jnp arrays, which
keeps pjit/shard_map sharding specs trivial to express.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from .config import ModelConfig

# ---------------------------------------------------------------- norms ----


def rms_norm(x, w, eps: float):
    dt = x.dtype
    x = x.astype(jnp.float32)
    y = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(dt)


def layer_norm(x, w, b, eps: float):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


def norm_init(cfg: ModelConfig, dim: int):
    if cfg.use_layernorm:
        return {"w": jnp.ones((dim,), jnp.float32), "b": jnp.zeros((dim,), jnp.float32)}
    return {"w": jnp.ones((dim,), jnp.float32)}


def norm_apply(cfg: ModelConfig, p, x):
    if cfg.use_layernorm:
        return layer_norm(x, p["w"], p["b"], cfg.norm_eps)
    return rms_norm(x, p["w"], cfg.norm_eps)


# ----------------------------------------------------------------- rope ----


def rope_freqs(hd: int, theta: float):
    return theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd) (hd even); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(ang)[..., None, :]                    # (..., S, 1, hd/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(n: int, d: int):
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10_000.0, dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ------------------------------------------------------------ activation ----


def activation_fn(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return jax.nn.gelu
    if name == "squared_relu":
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(f"unknown activation {name}")


# ----------------------------------------------------------- projections --


def proj(spec: str, x, w):
    """Weight projection: `einsum(spec, x, w)` accumulated in float32 and
    rounded once to the operands' dtype. A mesh that shards the contracted
    dim (TP output projections, FSDP) then all-reduces float32 partial sums,
    so a bf16 model decodes the same tokens sharded as on one device."""
    out = jnp.einsum(spec, x, w, preferred_element_type=jnp.float32)
    return out.astype(jnp.result_type(x.dtype, w.dtype))


# ------------------------------------------------------------------ mlp ----


def mlp_init(cfg: ModelConfig, key, d_ff: int | None = None):
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    std = 0.02
    p = {"w_down": jax.random.normal(k2, (ff, d), jnp.float32) * std / math.sqrt(2 * cfg.num_layers)}
    if cfg.gated_mlp:
        p["w_gate"] = jax.random.normal(k1, (d, ff), jnp.float32) * std
        p["w_up"] = jax.random.normal(k3, (d, ff), jnp.float32) * std
    else:
        p["w_in"] = jax.random.normal(k1, (d, ff), jnp.float32) * std
    return p


def no_constrain(x, kind):
    """The default sharding hook (one device): leaves `x` as it is."""
    return x


@jax.named_scope("mlp")
def mlp_apply(cfg: ModelConfig, p, x, constrain=no_constrain):
    act = activation_fn(cfg.activation)
    if cfg.gated_mlp:
        h = act(proj("...d,df->...f", x, p["w_gate"])) * \
            proj("...d,df->...f", x, p["w_up"])
    else:
        h = act(proj("...d,df->...f", x, p["w_in"]))
    return constrain(proj("...f,fd->...d", constrain(h, "gather"), p["w_down"]),
                     "gather")


# ------------------------------------------------------------ attention ----
# q is grouped for GQA: (B, S, Hkv, G, hd); k/v: (B, S, Hkv, hd).

_ATTN_OVERRIDE = None  # None | "dense" | "blockwise"  (roofline probes)


def set_attention_impl(mode):
    global _ATTN_OVERRIDE
    assert mode in (None, "dense", "blockwise"), mode
    _ATTN_OVERRIDE = mode


def _mask_bias(q_pos, kv_pos, causal: bool, kv_len=None):
    """Additive fp32 mask bias of shape (Sq, Skv)."""
    ok = jnp.ones((q_pos.shape[0], kv_pos.shape[0]), bool)
    if causal:
        ok &= kv_pos[None, :] <= q_pos[:, None]
    if kv_len is not None:
        ok &= kv_pos[None, :] < kv_len
    return jnp.where(ok, 0.0, -jnp.inf).astype(jnp.float32)


def sdpa(q, k, v, *, causal: bool, q_offset=0, kv_len=None, scale=None):
    """Dense grouped attention. q: (B,Sq,Hkv,G,hd); k,v: (B,Skv,Hkv,hd)."""
    B, Sq, Hkv, G, hd = q.shape
    Skv = k.shape[1]
    scale = scale or hd ** -0.5
    s = jnp.einsum("bqhgd,bshd->bhgqs", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    q_pos = q_offset + jnp.arange(Sq)
    kv_pos = jnp.arange(Skv)
    s = s + _mask_bias(q_pos, kv_pos, causal, kv_len)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqs,bshd->bqhgd", p.astype(v.dtype), v)
    return out


def blockwise_attention(q, k, v, *, causal: bool, q_block=512, kv_block=1024,
                        q_offset=0, scale=None):
    """Flash-style online-softmax attention in pure jnp (XLA path).

    Memory O(q_block*kv_block) instead of O(Sq*Skv); numerically identical to
    `sdpa`. This is the math the `flash_attention` Pallas kernel implements
    with VMEM tiles on real TPU; here it bounds the dry-run working set.
    """
    B, Sq, Hkv, G, hd = q.shape
    Skv = k.shape[1]
    vd = v.shape[-1]                       # may differ from hd (MLA)
    scale = scale or hd ** -0.5

    def pick_block(n, pref):
        if n <= pref:
            return n
        for cand in range(pref, 0, -1):    # largest divisor <= pref
            if n % cand == 0:
                return cand
        return n

    q_block = pick_block(Sq, min(q_block, Sq))
    kv_block = pick_block(Skv, min(kv_block, Skv))
    nq, nk = Sq // q_block, Skv // kv_block

    def one_q_block(qi):
        qb = lax.dynamic_slice_in_dim(q, qi * q_block, q_block, axis=1)
        q_pos = q_offset + qi * q_block + jnp.arange(q_block)

        def kv_step(carry, ki):
            m, l, acc = carry
            kb = lax.dynamic_slice_in_dim(k, ki * kv_block, kv_block, axis=1)
            vb = lax.dynamic_slice_in_dim(v, ki * kv_block, kv_block, axis=1)
            s = jnp.einsum("bqhgd,bshd->bhgqs", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            kv_pos = ki * kv_block + jnp.arange(kv_block)
            s = s + _mask_bias(q_pos, kv_pos, causal, None)
            m_new = jnp.maximum(m, s.max(axis=-1))
            # guard fully-masked rows (m_new = -inf)
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(jnp.isfinite(s), p, 0.0)
            alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l = l * alpha + p.sum(axis=-1)
            pv = jnp.einsum("bhgqs,bshd->bhgqd", p.astype(v.dtype), vb)
            acc = acc * alpha[..., None].astype(acc.dtype) + pv
            return (m_new, l, acc), None

        m0 = jnp.full((B, Hkv, G, q_block), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, q_block), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, q_block, vd), v.dtype)
        (m, l, acc), _ = lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None].astype(acc.dtype)
        return jnp.moveaxis(out, 3, 1)  # (B, q_block, Hkv, G, vd)

    outs = lax.map(one_q_block, jnp.arange(nq))          # (nq, B, qb, ...)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, Sq, Hkv, G, vd)
    return out


def decode_attention(q, k_cache, v_cache, cache_len, *, scale=None):
    """One-token attention against a (possibly padded) cache.

    q: (B, 1, Hkv, G, hd); caches: (B, Smax, Hkv, hd); cache_len: scalar or (B,).
    """
    hd = q.shape[-1]
    scale = scale or hd ** -0.5
    s = jnp.einsum("bqhgd,bshd->bhgqs", q, k_cache,
                   preferred_element_type=jnp.float32) * scale
    kv_pos = jnp.arange(k_cache.shape[1])
    length = jnp.asarray(cache_len)
    if length.ndim == 0:
        ok = kv_pos < length
        s = jnp.where(ok[None, None, None, None, :], s, -jnp.inf)
    else:
        ok = kv_pos[None, :] < length[:, None]
        s = jnp.where(ok[:, None, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqs,bshd->bqhgd", p.astype(v_cache.dtype), v_cache)
    return out


# Self-attention module (GQA, optional bias / qk-norm / rope / LoRA delta).


def attn_init(cfg: ModelConfig, key, *, cross: bool = False):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    std = 0.02
    p = {
        "wq": jax.random.normal(ks[0], (d, nq, hd), jnp.float32) * std,
        "wk": jax.random.normal(ks[1], (d, nkv, hd), jnp.float32) * std,
        "wv": jax.random.normal(ks[2], (d, nkv, hd), jnp.float32) * std,
        "wo": jax.random.normal(ks[3], (nq, hd, d), jnp.float32) * std / math.sqrt(2 * cfg.num_layers),
    }
    if cfg.attn_bias:
        p["bq"] = jnp.zeros((nq, hd), jnp.float32)
        p["bk"] = jnp.zeros((nkv, hd), jnp.float32)
        p["bv"] = jnp.zeros((nkv, hd), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def lora_init(cfg: ModelConfig, key, n_app: int):
    """Stacked per-application LoRA deltas for the zamba2 shared block."""
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq, nkv, r = cfg.n_heads, cfg.n_kv_heads, cfg.shared_lora_rank
    ks = jax.random.split(key, 8)
    z = lambda k, shape: jax.random.normal(k, shape, jnp.float32) * 0.02
    return {
        "a_q": z(ks[0], (n_app, d, r)), "b_q": jnp.zeros((n_app, r, nq * hd)),
        "a_k": z(ks[1], (n_app, d, r)), "b_k": jnp.zeros((n_app, r, nkv * hd)),
        "a_v": z(ks[2], (n_app, d, r)), "b_v": jnp.zeros((n_app, r, nkv * hd)),
        "a_o": z(ks[3], (n_app, d, r)), "b_o": jnp.zeros((n_app, r, d)),
    }


def _project_qkv(cfg: ModelConfig, p, x, lora=None):
    B, S, d = x.shape
    nq, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = proj("bsd,dhe->bshe", x, p["wq"])
    k = proj("bsd,dhe->bshe", x, p["wk"])
    v = proj("bsd,dhe->bshe", x, p["wv"])
    if lora is not None:
        q = q + ((x @ lora["a_q"]) @ lora["b_q"]).reshape(B, S, nq, hd)
        k = k + ((x @ lora["a_k"]) @ lora["b_k"]).reshape(B, S, nkv, hd)
        v = v + ((x @ lora["a_v"]) @ lora["b_v"]).reshape(B, S, nkv, hd)
    if cfg.attn_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def attn_apply(cfg: ModelConfig, p, x, *, positions, causal=True, lora=None,
               kv_override=None, block_threshold=8192,
               constrain=no_constrain):
    """Full-sequence self-attention (train / prefill). Returns (out, (k, v)).

    kv_override: (k, v) for cross-attention (already projected+rotated).
    """
    B, S, _ = x.shape
    nq, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    if kv_override is None:
        q, k, v = _project_qkv(cfg, p, x, lora)
        if cfg.use_rope:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
    else:
        q = proj("bsd,dhe->bshe", x, p["wq"])
        if cfg.attn_bias:
            q = q + p["bq"]
        k, v = kv_override
    G = nq // nkv
    qg = q.reshape(B, S, nkv, G, hd)
    dense = S * k.shape[1] <= block_threshold * block_threshold // 16 or S <= 2048
    if _ATTN_OVERRIDE is not None:
        dense = _ATTN_OVERRIDE == "dense"
    if dense:
        out = sdpa(qg, k, v, causal=causal)
    else:
        out = blockwise_attention(qg, k, v, causal=causal)
    out = out.reshape(B, S, nq, hd)
    out = constrain(proj("bshe,hed->bsd", constrain(out, "gather"), p["wo"]),
                    "gather")
    if lora is not None:
        flat = out  # LoRA on output proj applied to attention output
        out = out + (flat @ lora["a_o"]) @ lora["b_o"]
    return out, (k, v)


def cache_write(cache, new, pos):
    """Write one token's K/V at `pos` (scalar) or per-row positions ((B,))."""
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        return lax.dynamic_update_slice_in_dim(cache, new.astype(cache.dtype),
                                               pos, axis=1)
    B = cache.shape[0]
    return cache.at[jnp.arange(B), pos].set(new[:, 0].astype(cache.dtype))


def cache_write_chunk(cache, new, start):
    """Write a C-token chunk's K/V at positions [start, start+C). `start` is
    a scalar (chunked prefill is per-sequence: every row shares the offset)
    or a per-row (B,) vector (batched speculative verification: each row's
    chunk lands at its own decode position)."""
    start = jnp.asarray(start, jnp.int32)
    if start.ndim == 0:
        return lax.dynamic_update_slice(
            cache, new.astype(cache.dtype),
            (0, start) + (0,) * (cache.ndim - 2))

    C = new.shape[1]

    def one_row(c_row, n_row, s):               # (Smax, ...), (C, ...)
        # scatter with OOB *drop*, not dynamic_update_slice: a verify chunk
        # is fixed-width, so a row near the cache bound would otherwise have
        # its start clamped backward, silently overwriting valid earlier KV.
        # Real (acceptable) candidates are always in-bounds — only padding
        # positions ever fall past the end, and those must vanish.
        return c_row.at[s + jnp.arange(C)].set(n_row.astype(c_row.dtype),
                                               mode="drop")
    return jax.vmap(one_row)(cache, new, start)


def chunk_positions(start, B: int, C: int):
    """(B, C) query positions for a chunk at `start` (scalar or (B,))."""
    start = jnp.asarray(start, jnp.int32)
    pos = jnp.reshape(start, (-1, 1)) + jnp.arange(C, dtype=jnp.int32)
    return jnp.broadcast_to(pos, (B, C))


def attn_chunk_apply(cfg: ModelConfig, p, x, *, start, k_cache, v_cache,
                     lora=None, cross=False, constrain=no_constrain):
    """Chunked-prefill attention: C query tokens at positions
    [start, start+C) attend the cache up to their own position (causal
    within the chunk, full over the already-filled prefix). Generalizes
    `attn_decode_apply` from C=1; `start` may be traced, so one jit
    signature serves every chunk offset.

    x: (B, C, d). Caches (B, Smax, Hkv, hd). Returns (out, (k_cache, v_cache))
    with the chunk's K/V written into the caches (cross: caches untouched).
    `start` may also be a per-row (B,) vector (batched speculative
    verification: every row's chunk sits at its own decode position).
    """
    B, C, _ = x.shape
    nq, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q_pos = chunk_positions(start, B, C)                      # (B, C)
    with jax.named_scope("qkv_proj"):
        if cross:
            q = proj("bsd,dhe->bshe", x, p["wq"])
            if cfg.attn_bias:
                q = q + p["bq"]
        else:
            q, k, v = _project_qkv(cfg, p, x, lora)
            if cfg.use_rope:
                q = apply_rope(q, q_pos, cfg.rope_theta)
                k = apply_rope(k, q_pos, cfg.rope_theta)
    with jax.named_scope("attention"):
        if not cross:
            k_cache = cache_write_chunk(k_cache, k, start)
            v_cache = cache_write_chunk(v_cache, v, start)
        qg = q.reshape(B, C, nkv, nq // nkv, hd)
        s = jnp.einsum("bqhgd,bshd->bhgqs", qg, k_cache,
                       preferred_element_type=jnp.float32) * (hd ** -0.5)
        kv_pos = jnp.arange(k_cache.shape[1])
        ok = (kv_pos[None, None, :] <= q_pos[:, :, None]) if not cross else \
            jnp.ones((B, C, k_cache.shape[1]), bool)
        s = jnp.where(ok[:, None, None, :, :], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhgqs,bshd->bqhgd", pr.astype(v_cache.dtype),
                         v_cache)
        out = out.reshape(B, C, nq, hd)
    with jax.named_scope("out_proj"):
        out = constrain(proj("bshe,hed->bsd", constrain(out, "gather"),
                             p["wo"]), "gather")
    if lora is not None:
        out = out + (out @ lora["a_o"]) @ lora["b_o"]
    return out, (k_cache, v_cache) if not cross else (None, None)


def attn_decode_apply(cfg: ModelConfig, p, x, *, pos, k_cache, v_cache, lora=None,
                      cross=False, cache_len=None, attn_impl=None,
                      constrain=no_constrain):
    """Single-token decode. x: (B, 1, d). Caches (B, Smax, Hkv, hd).
    `pos` may be a scalar or a per-row (B,) vector (continuous batching).

    Returns (out, (k_new, v_new)) — k_new/v_new are this step's projections
    (None for cross-attention); caller owns the cache update.
    """
    B, S, _ = x.shape
    nq, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    with jax.named_scope("qkv_proj"):
        if cross:
            q = proj("bsd,dhe->bshe", x, p["wq"])
            if cfg.attn_bias:
                q = q + p["bq"]
        else:
            q, k, v = _project_qkv(cfg, p, x, lora)
            if cfg.use_rope:
                pp = jnp.broadcast_to(
                    jnp.asarray(pos).reshape(-1, 1) if jnp.asarray(pos).ndim
                    else jnp.full((B, S), pos), (B, S))
                q = apply_rope(q, pp, cfg.rope_theta)
                k = apply_rope(k, pp, cfg.rope_theta)
    with jax.named_scope("attention"):
        if cross:
            k_new = v_new = None
            length = k_cache.shape[1] if cache_len is None else cache_len
        else:
            k_new, v_new = k, v
            k_cache = cache_write(k_cache, k, pos)
            v_cache = cache_write(v_cache, v, pos)
            length = pos + 1
        qg = q.reshape(B, S, nkv, nq // nkv, hd)
        impl = attn_impl or decode_attention
        out = impl(qg, k_cache, v_cache, length)
        out = out.reshape(B, S, nq, hd)
    with jax.named_scope("out_proj"):
        out = constrain(proj("bshe,hed->bsd", constrain(out, "gather"),
                             p["wo"]), "gather")
    if lora is not None:
        out = out + (out @ lora["a_o"]) @ lora["b_o"]
    return out, (k_cache, v_cache) if not cross else (None, None)


# ---------------------------------------------------------------- MLA ------


def mla_init(cfg: ModelConfig, key):
    d, H = cfg.d_model, cfg.n_heads
    r, nd, rd, vd = cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 5)
    std = 0.02
    return {
        "wq": jax.random.normal(ks[0], (d, H, nd + rd), jnp.float32) * std,
        "w_dkv": jax.random.normal(ks[1], (d, r + rd), jnp.float32) * std,
        "w_uk": jax.random.normal(ks[2], (r, H, nd), jnp.float32) * std,
        "w_uv": jax.random.normal(ks[3], (r, H, vd), jnp.float32) * std,
        "wo": jax.random.normal(ks[4], (H, vd, d), jnp.float32) * std / math.sqrt(2 * cfg.num_layers),
        "kv_norm": jnp.ones((r,), jnp.float32),
    }


def mla_project(cfg: ModelConfig, p, x, positions):
    """Shared q / compressed-kv projections. Returns q_nope,q_rope,c_kv,k_rope."""
    nd, rd = cfg.qk_nope_dim, cfg.qk_rope_dim
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    dkv = x @ p["w_dkv"]
    c_kv, k_rope = dkv[..., : cfg.kv_lora_rank], dkv[..., cfg.kv_lora_rank:]
    c_kv = rms_norm(c_kv, p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[..., None, :], positions, cfg.rope_theta)[..., 0, :]
    return q_nope, q_rope, c_kv, k_rope


def mla_apply(cfg: ModelConfig, p, x, *, positions):
    """Full-sequence MLA (train/prefill). Returns (out, (c_kv, k_rope))."""
    B, S, _ = x.shape
    H, vd = cfg.n_heads, cfg.v_head_dim
    q_nope, q_rope, c_kv, k_rope = mla_project(cfg, p, x, positions)
    k_nope = jnp.einsum("bsr,rhe->bshe", c_kv, p["w_uk"])
    v = jnp.einsum("bsr,rhe->bshe", c_kv, p["w_uv"])
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, cfg.qk_rope_dim))], axis=-1)
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    qg = q[:, :, :, None, :]
    dense = S <= 2048
    if _ATTN_OVERRIDE is not None:
        dense = _ATTN_OVERRIDE == "dense"
    if dense:
        out = sdpa(qg, k, v, causal=True, scale=scale)
    else:
        out = blockwise_attention(qg, k, v, causal=True, scale=scale)
    out = out.reshape(B, S, H, vd)
    out = jnp.einsum("bshe,hed->bsd", out, p["wo"])
    return out, (c_kv, k_rope)


def mla_decode_apply(cfg: ModelConfig, p, x, *, pos, ckv_cache, krope_cache):
    """Absorbed-matmul MLA decode (DeepSeek-V2's own optimization): the
    per-head K/V up-projections fold into the query/context sides so the
    cache stays compressed (r + rope_dim per token). `pos` scalar or (B,)."""
    B, S, _ = x.shape
    H = cfg.n_heads
    pp = jnp.broadcast_to(jnp.asarray(pos).reshape(-1, 1) if jnp.asarray(pos).ndim
                          else jnp.full((B, S), pos), (B, S))
    q_nope, q_rope, c_kv, k_rope = mla_project(cfg, p, x, pp)
    ckv_cache = cache_write(ckv_cache, c_kv, pos)
    krope_cache = cache_write(krope_cache, k_rope, pos)
    q_abs = jnp.einsum("bshe,rhe->bshr", q_nope, p["w_uk"])          # (B,1,H,r)
    s = jnp.einsum("bshr,btr->bhst", q_abs, ckv_cache, preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bshe,bte->bhst", q_rope, krope_cache, preferred_element_type=jnp.float32)
    s = s * (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    posv = jnp.asarray(pos)
    if posv.ndim == 0:
        ok = jnp.arange(ckv_cache.shape[1])[None] <= posv
    else:
        ok = jnp.arange(ckv_cache.shape[1])[None, :] <= posv[:, None]
    s = jnp.where(ok[:, None, None, :], s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhst,btr->bshr", pr.astype(ckv_cache.dtype), ckv_cache)
    out = jnp.einsum("bshr,rhe->bshe", ctx, p["w_uv"])
    out = jnp.einsum("bshe,hed->bsd", out, p["wo"])
    return out, (ckv_cache, krope_cache)


def mla_chunk_apply(cfg: ModelConfig, p, x, *, start, ckv_cache, krope_cache):
    """Chunked-prefill MLA (absorbed form, same math as `mla_decode_apply`
    with C query tokens): the chunk's compressed KV is written at
    [start, start+C) and queries attend the cache up to their own position.
    `start` may be a scalar or a per-row (B,) vector (batched verify)."""
    B, C, _ = x.shape
    q_pos = chunk_positions(start, B, C)                      # (B, C)
    q_nope, q_rope, c_kv, k_rope = mla_project(cfg, p, x, q_pos)
    ckv_cache = cache_write_chunk(ckv_cache, c_kv, start)
    krope_cache = cache_write_chunk(krope_cache, k_rope, start)
    q_abs = jnp.einsum("bshe,rhe->bshr", q_nope, p["w_uk"])
    s = jnp.einsum("bshr,btr->bhst", q_abs, ckv_cache,
                   preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bshe,bte->bhst", q_rope, krope_cache,
                       preferred_element_type=jnp.float32)
    s = s * (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    ok = jnp.arange(ckv_cache.shape[1])[None, None, :] <= q_pos[:, :, None]
    s = jnp.where(ok[:, None, :, :], s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhst,btr->bshr", pr.astype(ckv_cache.dtype), ckv_cache)
    out = jnp.einsum("bshr,rhe->bshe", ctx, p["w_uv"])
    out = jnp.einsum("bshe,hed->bsd", out, p["wo"])
    return out, (ckv_cache, krope_cache)


# ---------------------------------------------------------------- MoE ------

_MOE_GROUPS = 0  # >1: grouped-local dispatch (expert-parallel layouts)


def set_moe_groups(g):
    global _MOE_GROUPS
    _MOE_GROUPS = int(g)


def moe_init(cfg: ModelConfig, key):
    d, E, ff = cfg.d_model, cfg.n_experts, cfg.expert_d_ff or cfg.d_ff
    ks = jax.random.split(key, 5)
    std = 0.02
    p = {
        "router": jax.random.normal(ks[0], (d, E), jnp.float32) * std,
        "w_gate": jax.random.normal(ks[1], (E, d, ff), jnp.float32) * std,
        "w_up": jax.random.normal(ks[2], (E, d, ff), jnp.float32) * std,
        "w_down": jax.random.normal(ks[3], (E, ff, d), jnp.float32) * std / math.sqrt(2 * cfg.num_layers),
    }
    if cfg.n_shared_experts:
        sh_ff = ff * cfg.n_shared_experts
        sub = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": jax.random.normal(sub[0], (d, sh_ff), jnp.float32) * std,
            "w_up": jax.random.normal(sub[1], (d, sh_ff), jnp.float32) * std,
            "w_down": jax.random.normal(sub[2], (sh_ff, d), jnp.float32) * std / math.sqrt(2 * cfg.num_layers),
        }
    return p


def moe_capacity(cfg: ModelConfig, T: int) -> int:
    C = int(math.ceil(cfg.capacity_factor * cfg.moe_top_k * T / cfg.n_experts))
    return max(8, -(-C // 8) * 8)  # round up to multiple of 8


def _moe_dispatch_group(cfg: ModelConfig, p, x2, C):
    """Dispatch+compute+combine for one token group (no cross-group refs:
    under a (groups=data-shards) reshape every index op stays shard-local)."""
    T, d = x2.shape
    E, K = cfg.n_experts, cfg.moe_top_k
    logits = (x2 @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = lax.top_k(probs, K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    flat_e = top_i.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), K)
    flat_w = top_w.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1, flat_e[:, None], axis=1)[:, 0]
    keep = pos < C
    slot = jnp.where(keep, flat_e * C + pos, E * C)
    xe = jnp.zeros((E * C + 1, d), x2.dtype).at[slot].set(x2[flat_t])
    xe = xe[: E * C].reshape(E, C, d)
    act = activation_fn(cfg.activation)
    h = act(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])) *         jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    ye_flat = jnp.concatenate([ye.reshape(E * C, d), jnp.zeros((1, d), ye.dtype)], axis=0)
    back = ye_flat[slot] * (flat_w * keep)[:, None].astype(ye.dtype)
    return jnp.zeros((T, d), x2.dtype).at[flat_t].add(back)


def moe_apply(cfg: ModelConfig, p, x, *, return_aux=False, constrain=None):
    """Capacity-based top-k MoE with gather/scatter dispatch (no giant one-hot
    einsums). x: (B, S, d). Tokens over capacity are dropped (GShard-style).

    `constrain(x, kind)` hook: under expert parallelism the launcher pins
    the dispatch buffer to P(data, None, None) (experts sharded over data) so
    the scatter becomes a token all-to-all instead of index all-gathers."""
    B, S, d = x.shape
    if _MOE_GROUPS > 1 and (B * S) % _MOE_GROUPS == 0:
        G = _MOE_GROUPS
        xg = x.reshape(G, B * S // G, d)
        C_g = moe_capacity(cfg, B * S // G)
        y = jax.vmap(lambda xx: _moe_dispatch_group(cfg, p, xx, C_g))(xg)
        if constrain is not None:
            y = constrain(y, "moe_grouped")
        y = y.reshape(B * S, d)
        if cfg.n_shared_experts:
            sp = p["shared"]
            act = activation_fn(cfg.activation)
            x2s = x.reshape(B * S, d)
            y = y + (act(x2s @ sp["w_gate"]) * (x2s @ sp["w_up"])) @ sp["w_down"]
        y = y.reshape(B, S, d)
        if not return_aux:
            return y
        return y, jnp.float32(0.0)
    x2 = x.reshape(B * S, d)
    T, E, K = B * S, cfg.n_experts, cfg.moe_top_k
    C = moe_capacity(cfg, T)

    logits = (x2 @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = lax.top_k(probs, K)                     # (T, K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    flat_e = top_i.reshape(-1)                             # (T*K,)
    flat_t = jnp.repeat(jnp.arange(T), K)
    flat_w = top_w.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)    # (T*K, E)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1, flat_e[:, None], axis=1)[:, 0]
    keep = pos < C
    slot = jnp.where(keep, flat_e * C + pos, E * C)        # E*C = drop slot

    xe = jnp.zeros((E * C + 1, d), x.dtype).at[slot].set(x2[flat_t])
    xe = xe[: E * C].reshape(E, C, d)
    if constrain is not None:
        xe = constrain(xe, "moe_dispatch")
    act = activation_fn(cfg.activation)
    h = act(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])) * jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"])        # (E, C, d)
    if constrain is not None:
        ye = constrain(ye, "moe_dispatch")

    ye_flat = jnp.concatenate([ye.reshape(E * C, d), jnp.zeros((1, d), ye.dtype)], axis=0)
    back = ye_flat[slot] * (flat_w * keep)[:, None].astype(ye.dtype)
    y = jnp.zeros((T, d), x.dtype).at[flat_t].add(back)

    if cfg.n_shared_experts:
        sp = p["shared"]
        y = y + (act(x2 @ sp["w_gate"]) * (x2 @ sp["w_up"])) @ sp["w_down"]

    y = y.reshape(B, S, d)
    if not return_aux:
        return y
    # load-balance aux loss (Switch/GShard): E * sum_e f_e * P_e
    frac_tokens = jnp.mean(jax.nn.one_hot(top_i[:, 0], E, dtype=jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs)
    return y, aux
