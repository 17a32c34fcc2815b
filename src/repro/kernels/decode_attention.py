"""Pallas TPU flash-decoding (single-token attention over a long KV cache).

One query token per (batch, head); the KV sequence is tiled and reduced
sequentially with online-softmax accumulators in VMEM scratch. Padded cache
positions (>= length) are masked. This kernel is the per-device leaf of the
sequence-sharded decode path (distributed/decode.py): shard_map splits S
over the `model` mesh axis, each device runs this kernel on its shard, and
the partial (max, denom, acc) combine happens with tiny collectives.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            bk: int, nk: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[0]
    q = q_ref[0, 0, :].astype(jnp.float32)                  # (D,)
    k = k_ref[0, :, 0, :].astype(jnp.float32)               # (bk, D)
    v = v_ref[0, :, 0, :].astype(jnp.float32)
    s = jnp.sum(k * q[None, :], axis=1) * (q.shape[0] ** -0.5)   # (bk,)
    pos = j * bk + jax.lax.iota(jnp.int32, bk)
    s = jnp.where(pos < length, s, NEG_INF)

    m_prev = m_scr[0, 0]
    m_new = jnp.maximum(m_prev, s.max())
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[0, 0] = l_scr[0, 0] * alpha + p.sum()
    acc_scr[0, :] = acc_scr[0, :] * alpha + jnp.sum(p[:, None] * v, axis=0)
    m_scr[0, 0] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        o_ref[0, 0, :] = (acc_scr[0, :] / jnp.maximum(l_scr[0, 0], 1e-30)
                          ).astype(o_ref.dtype)


# ------------------------------------------------------------ paged --------
#
# Paged flash-decoding: the KV cache is a pool of fixed-size pages shared by
# every sequence (serving/engine.py kv_layout="paged"); each row owns a page
# *table* mapping its block index to a physical page. The table rides in as
# a scalar-prefetch operand, so the KV BlockSpec index_map dereferences it —
# the kernel walks pages in logical order without ever materializing a
# gathered copy of the cache (the host-side reference path, `cache_ops.
# gather_page_views`, pays that copy; this kernel is why TPUs don't).


def _paged_kernel(len_ref, ptab_ref, q_ref, k_ref, v_ref, o_ref, m_scr,
                  l_scr, acc_scr, *, ps: int, nk: int):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]
    q = q_ref[0, 0, :].astype(jnp.float32)                  # (D,)
    k = k_ref[0, :, 0, :].astype(jnp.float32)               # (ps, D)
    v = v_ref[0, :, 0, :].astype(jnp.float32)
    s = jnp.sum(k * q[None, :], axis=1) * (q.shape[0] ** -0.5)   # (ps,)
    pos = j * ps + jax.lax.iota(jnp.int32, ps)              # logical positions
    s = jnp.where(pos < length, s, NEG_INF)

    m_prev = m_scr[0, 0]
    m_new = jnp.maximum(m_prev, s.max())
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[0, 0] = l_scr[0, 0] * alpha + p.sum()
    acc_scr[0, :] = acc_scr[0, :] * alpha + jnp.sum(p[:, None] * v, axis=0)
    m_scr[0, 0] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        o_ref[0, 0, :] = (acc_scr[0, :] / jnp.maximum(l_scr[0, 0], 1e-30)
                          ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas(q, k_pool, v_pool, page_table, lengths, *,
                                  interpret=False):
    """Flash-decoding through a page table.

    q: (B, H, D); pools: (P, ps, Hkv, D) — the *shared* page pool, no batch
    axis; page_table: (B, nb) int32 physical page per logical block;
    lengths: (B,) valid tokens per row. -> (B, H, D).
    """
    B, H, D = q.shape
    P, ps, Hkv, _ = k_pool.shape
    G = H // Hkv
    nb = page_table.shape[1]

    grid = (B, H, nb)
    kernel = functools.partial(_paged_kernel, ps=ps, nk=nb)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, D), lambda b, h, j, lens, ptab: (b, h, 0)),
                pl.BlockSpec((1, ps, 1, D),
                             lambda b, h, j, lens, ptab: (ptab[b, j], 0, h // G, 0)),
                pl.BlockSpec((1, ps, 1, D),
                             lambda b, h, j, lens, ptab: (ptab[b, j], 0, h // G, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, D), lambda b, h, j, lens, ptab: (b, h, 0)),
            scratch_shapes=[
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(lengths, jnp.int32), jnp.asarray(page_table, jnp.int32),
      q, k_pool, v_pool)
    return out


def _paged_verify_kernel(start_ref, ptab_ref, q_ref, k_ref, v_ref, o_ref,
                         m_scr, l_scr, acc_scr, *, ps: int, nk: int):
    """Batched-verify flash-decoding: C candidate tokens per (batch, head)
    attend the row's paged KV causally from its decode position. The online
    softmax accumulators carry one (max, denom, acc) row per candidate."""
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    start = start_ref[b]
    q = q_ref[0, 0, :, :].astype(jnp.float32)               # (C, D)
    k = k_ref[0, :, 0, :].astype(jnp.float32)               # (ps, D)
    v = v_ref[0, :, 0, :].astype(jnp.float32)
    C = q.shape[0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) \
        * (q.shape[1] ** -0.5)                              # (C, ps)
    kv_pos = j * ps + jax.lax.broadcasted_iota(jnp.int32, (C, ps), 1)
    q_pos = start + jax.lax.broadcasted_iota(jnp.int32, (C, ps), 0)
    s = jnp.where(kv_pos <= q_pos, s, NEG_INF)              # causal per row

    m_prev = m_scr[:, 0]                                    # (C,)
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_scr[:, 0] = l_scr[:, 0] * alpha + p.sum(axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jnp.dot(p, v)
    m_scr[:, 0] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        o_ref[0, 0, :, :] = (acc_scr[...] /
                             jnp.maximum(l_scr[:, 0], 1e-30)[:, None]
                             ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_verify_attention_pallas(q, k_pool, v_pool, page_table, starts, *,
                                  interpret=False):
    """Speculative-verification attention through a page table
    (DESIGN.md §14): every row scores its C candidate tokens (pending +
    drafts, already written to the row's pages at [starts[b], starts[b]+C))
    in one pass — the batched generalization of flash-decoding from C=1.

    q: (B, H, C, D); pools: (P, ps, Hkv, D) shared page pool; page_table:
    (B, nb) int32; starts: (B,) decode position of each row's first
    candidate. -> (B, H, C, D).
    """
    B, H, C, D = q.shape
    P, ps, Hkv, _ = k_pool.shape
    G = H // Hkv
    nb = page_table.shape[1]

    grid = (B, H, nb)
    kernel = functools.partial(_paged_verify_kernel, ps=ps, nk=nb)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, C, D),
                             lambda b, h, j, starts, ptab: (b, h, 0, 0)),
                pl.BlockSpec((1, ps, 1, D),
                             lambda b, h, j, starts, ptab: (ptab[b, j], 0, h // G, 0)),
                pl.BlockSpec((1, ps, 1, D),
                             lambda b, h, j, starts, ptab: (ptab[b, j], 0, h // G, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, C, D),
                                   lambda b, h, j, starts, ptab: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((C, 1), jnp.float32),
                pltpu.VMEM((C, 1), jnp.float32),
                pltpu.VMEM((C, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, C, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(starts, jnp.int32), jnp.asarray(page_table, jnp.int32),
      q, k_pool, v_pool)
    return out


def paged_verify_attention_ref(q, k_pool, v_pool, page_table, starts):
    """jnp oracle: gather pages into dense rows, causal masked attention."""
    B, H, C, D = q.shape
    _, ps, Hkv, _ = k_pool.shape
    kg = k_pool[page_table]
    vg = v_pool[page_table]
    S = kg.shape[1] * ps
    kg = kg.reshape(B, S, Hkv, D)
    vg = vg.reshape(B, S, Hkv, D)
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, C, D)
    s = jnp.einsum("bhgcd,bshd->bhgcs", qg, kg,
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    q_pos = jnp.asarray(starts)[:, None] + jnp.arange(C)[None, :]   # (B, C)
    ok = jnp.arange(S)[None, None, :] <= q_pos[:, :, None]          # (B, C, S)
    s = jnp.where(ok[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgcs,bshd->bhgcd", p.astype(vg.dtype), vg)
    return out.reshape(B, H, C, D)


def paged_decode_attention_ref(q, k_pool, v_pool, page_table, lengths):
    """jnp oracle: gather pages into dense rows, then masked attention."""
    B, H, D = q.shape
    _, ps, Hkv, _ = k_pool.shape
    kg = k_pool[page_table]                     # (B, nb, ps, Hkv, D)
    vg = v_pool[page_table]
    S = kg.shape[1] * ps
    kg = kg.reshape(B, S, Hkv, D)
    vg = vg.reshape(B, S, Hkv, D)
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, kg,
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    ok = jnp.arange(S)[None, :] < jnp.asarray(lengths)[:, None]
    s = jnp.where(ok[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgs,bshd->bhgd", p.astype(vg.dtype), vg).reshape(B, H, D)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def decode_attention_pallas(q, k_cache, v_cache, length, *, bk=512,
                            interpret=False):
    """q: (B, H, D); caches: (B, S, Hkv, D); length: scalar int. -> (B, H, D)."""
    B, H, D = q.shape
    _, S, Hkv, _ = k_cache.shape
    G = H // Hkv
    bk = min(bk, S)
    assert S % bk == 0, (S, bk)
    nk = S // bk
    lengths = jnp.full((1,), length, jnp.int32)

    grid = (B, H, nk)
    kernel = functools.partial(_kernel, bk=bk, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, D), lambda b, h, j, lens: (b, h, 0)),
                pl.BlockSpec((1, bk, 1, D), lambda b, h, j, lens: (b, j, h // G, 0)),
                pl.BlockSpec((1, bk, 1, D), lambda b, h, j, lens: (b, j, h // G, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, D), lambda b, h, j, lens: (b, h, 0)),
            scratch_shapes=[
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, q, k_cache, v_cache)
    return out
