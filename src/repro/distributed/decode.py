"""Sequence-sharded decode attention: flash-decoding as an ICI collective.

The KV cache's sequence dim is sharded over the `model` axis. Each device
computes attention over its local KV shard, producing partial
(max m, denom l, weighted-sum acc); the cross-shard combine is three tiny
collectives:

    m*   = pmax(m)
    l*   = psum(l * exp(m - m*))
    out  = psum(acc * exp(m - m*)) / l*

vs. the GSPMD baseline, which reduces over the *masked score tensor* along
the sharded axis (wire O(B*H*S/shards)). Here the wire carries
O(B*H*head_dim) — independent of S. This is the decode hillclimb lever for
decode_32k / long_500k (EXPERIMENTS.md §Perf).

Composition: `make_seq_sharded_decode_attn(mesh)` returns an attn_impl for
`models.decode_step`; it shard_maps ONLY the attention op (manual over
`model`, every other axis stays under GSPMD), so the surrounding model code
is untouched.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import shard_map


def _partial_attn(axis, q, k_shard, v_shard, length):
    """Local partial attention + combine. q: (B,1,Hkv,G,hd) replicated;
    k/v_shard: (B, S_loc, Hkv, hd) = this device's sequence shard.
    `axis` may be one name or a tuple (major..minor order of the sharded
    sequence dim)."""
    hd = q.shape[-1]
    scale = hd ** -0.5
    axes = axis if isinstance(axis, tuple) else (axis,)
    idx = 0
    for a in axes:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    s_loc = k_shard.shape[1]
    start = idx * s_loc
    s = jnp.einsum("bqhgd,bshd->bhgqs", q, k_shard,
                   preferred_element_type=jnp.float32) * scale
    pos = start + jnp.arange(s_loc)
    lengthv = jnp.asarray(length)
    ok = (pos[None, :] < lengthv[:, None]) if lengthv.ndim else (pos < lengthv)[None, :]
    s = jnp.where(ok[:, None, None, None, :], s, -jnp.inf)
    m = s.max(axis=-1)
    m_star = jax.lax.pmax(m, axes)
    m_safe = jnp.where(jnp.isfinite(m_star), m_star, 0.0)
    p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe[..., None]), 0.0)
    l = p.sum(axis=-1)
    acc = jnp.einsum("bhgqs,bshd->bhgqd", p.astype(v_shard.dtype), v_shard)
    l_star = jax.lax.psum(l, axes)
    out = jax.lax.psum(acc, axes)
    out = out / jnp.maximum(l_star, 1e-30)[..., None].astype(out.dtype)
    return jnp.moveaxis(out, 3, 1)           # (B,1,Hkv,G,hd)


def make_seq_sharded_decode_attn(mesh, axis="model",
                                 batch_axis: str | None = "data"):
    """attn_impl for models.decode_step / layers.attn_decode_apply.

    Caches must be sharded P(batch_axis, axis, None, None) on (B, S, Hkv, hd);
    `axis` may be a tuple for combined-axis sequence sharding (ws2d layout:
    batch replicated, S over (data, model))."""
    axes = axis if isinstance(axis, tuple) else (axis,)
    b = batch_axis if (batch_axis and batch_axis in mesh.axis_names
                       and batch_axis not in axes) else None

    def attn(q, k_cache, v_cache, length):
        lengthv = jnp.asarray(length)
        len_spec = P(b) if lengthv.ndim else P()
        fn = shard_map(
            partial(_partial_attn, axes),
            mesh=mesh,
            in_specs=(P(b, None, None, None, None),
                      P(b, axis, None, None),
                      P(b, axis, None, None),
                      len_spec),
            out_specs=P(b, None, None, None, None),
            axis_names=set(axes) | ({b} if b else set()),
            check_vma=False,
        )
        return fn(q, k_cache, v_cache,
                  lengthv if lengthv.ndim else lengthv[None])

    return attn
