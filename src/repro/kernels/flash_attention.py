"""Pallas TPU flash attention (prefill hot spot).

Tiling: grid (B, H, Sq/bq, Skv/bk); the innermost kv-block axis is
sequential ("arbitrary") so the online-softmax accumulators live in VMEM
scratch across kv steps. Causal blocks that are fully masked are *skipped*
(pl.when on block indices) — this is the 2x FLOP saving the XLA jnp path
cannot express (DESIGN.md §5). GQA is handled in the k/v index maps
(q head h reads kv head h // G). Block sizes are MXU-aligned (128 lanes).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            causal: bool, scale: float, bq: int, bk: int, nk: int):
    i = pl.program_id(2)          # q block
    j = pl.program_id(3)          # kv block (sequential)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute():
        q = q_ref[0, :, 0, :].astype(jnp.float32)          # (bq, D)
        k = k_ref[0, :, 0, :].astype(jnp.float32)          # (bk, D)
        v = v_ref[0, :, 0, :].astype(jnp.float32)          # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:, 0] = l_scr[:, 0] * alpha + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[:, 0] = m_new

    if causal:
        # skip kv blocks entirely above the diagonal
        pl.when(j * bk <= (i + 1) * bq - 1)(_compute)
    else:
        _compute()

    last_j = ((i + 1) * bq - 1) // bk if causal else nk - 1

    @pl.when(j == last_j)
    def _finalize():
        denom = jnp.maximum(l_scr[:, 0], 1e-30)
        o_ref[0, :, 0, :] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention_pallas(q, k, v, *, causal=True, bq=128, bk=128,
                           interpret=False):
    """q: (B, Sq, H, D); k/v: (B, Skv, Hkv, D). Returns (B, Sq, H, D)."""
    B, Sq, H, D = q.shape
    _, Skv, Hkv, _ = k.shape
    G = H // Hkv
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, Skv, bq, bk)
    nq, nk = Sq // bq, Skv // bk
    scale = D ** -0.5

    grid = (B, H, nq, nk)
    kernel = functools.partial(_kernel, causal=causal, scale=scale,
                               bq=bq, bk=bk, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, 1, D), lambda b, h, i, j: (b, i, h, 0)),
            pl.BlockSpec((1, bk, 1, D), lambda b, h, i, j: (b, j, h // G, 0)),
            pl.BlockSpec((1, bk, 1, D), lambda b, h, i, j: (b, j, h // G, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, D), lambda b, h, i, j: (b, i, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Sq, H, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max
            pltpu.VMEM((bq, 1), jnp.float32),    # running denom
            pltpu.VMEM((bq, D), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out
