"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal: bool = True, scale=None):
    """q: (B, Sq, H, d); k/v: (B, Skv, Hkv, d) with H % Hkv == 0."""
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    scale = scale or D ** -0.5
    qg = q.reshape(B, Sq, Hkv, G, D)
    s = jnp.einsum("bqhgd,bshd->bhgqs", qg, k, preferred_element_type=jnp.float32) * scale
    if causal:
        Skv = k.shape[1]
        mask = jnp.arange(Skv)[None, :] <= (jnp.arange(Sq)[:, None] + (Skv - Sq))
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqs,bshd->bqhgd", p.astype(v.dtype), v)
    return out.reshape(B, Sq, H, D)


def decode_attention_ref(q, k_cache, v_cache, length):
    """q: (B, H, d); caches: (B, S, Hkv, d); length: scalar valid length."""
    B, H, D = q.shape
    Hkv = k_cache.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k_cache,
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    ok = jnp.arange(k_cache.shape[1]) < length
    s = jnp.where(ok[None, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p.astype(v_cache.dtype), v_cache)
    return out.reshape(B, H, D)


def ssm_scan_ref(x, dt, A, B_mat, C_mat, D, h0=None):
    """Mamba1 selective scan oracle. Shapes as repro.models.ssm.mamba1_scan_ref."""
    from repro.models.ssm import mamba1_scan_ref
    return mamba1_scan_ref(x, dt, A, B_mat, C_mat, D, h0=h0)


def moe_gating_ref(logits, k: int):
    """logits: (T, E). Returns (weights (T,k) renormalized, indices (T,k))."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, i = jax.lax.top_k(probs, k)
    return w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9), i
