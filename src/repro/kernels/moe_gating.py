"""Pallas TPU fused MoE gating: softmax -> top-k -> renormalize.

One pass over the router logits per token tile; iterative arg-max selection
(k is small) avoids a full sort. Outputs renormalized top-k weights and
expert indices, matching `ref.moe_gating_ref`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG = -1e30


def _kernel(x_ref, w_ref, i_ref, *, k: int):
    logits = x_ref[...].astype(jnp.float32)                 # (bt, E)
    m = logits.max(axis=1, keepdims=True)
    p = jnp.exp(logits - m)
    p = p / p.sum(axis=1, keepdims=True)

    def pick(_, carry):
        probs, ws, ids, slot = carry
        top = probs.max(axis=1)
        arg = jnp.argmax(probs, axis=1)
        ws = jax.lax.dynamic_update_slice_in_dim(ws, top[:, None], slot, axis=1)
        ids = jax.lax.dynamic_update_slice_in_dim(ids, arg[:, None].astype(jnp.int32),
                                                  slot, axis=1)
        onehot = jax.nn.one_hot(arg, probs.shape[1], dtype=probs.dtype)
        return probs - onehot * (top[:, None] + 1.0), ws, ids, slot + 1

    bt = p.shape[0]
    ws0 = jnp.zeros((bt, k), jnp.float32)
    ids0 = jnp.zeros((bt, k), jnp.int32)
    _, ws, ids, _ = jax.lax.fori_loop(0, k, pick, (p, ws0, ids0, 0))
    ws = jnp.maximum(ws, 0.0)
    w_ref[...] = ws / jnp.maximum(ws.sum(axis=1, keepdims=True), 1e-9)
    i_ref[...] = ids


@functools.partial(jax.jit, static_argnames=("k", "bt", "interpret"))
def moe_gating_pallas(logits, k: int, *, bt=256, interpret=False):
    """logits: (T, E). Returns (weights (T,k), idx (T,k))."""
    T, E = logits.shape
    bt = min(bt, T)
    pad = (-T) % bt
    if pad:
        logits = jnp.pad(logits, ((0, pad), (0, 0)), constant_values=NEG)
    Tp = logits.shape[0]
    w, i = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=(Tp // bt,),
        in_specs=[pl.BlockSpec((bt, E), lambda t: (t, 0))],
        out_specs=[pl.BlockSpec((bt, k), lambda t: (t, 0)),
                   pl.BlockSpec((bt, k), lambda t: (t, 0))],
        out_shape=[jax.ShapeDtypeStruct((Tp, k), jnp.float32),
                   jax.ShapeDtypeStruct((Tp, k), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(logits)
    return w[:T], i[:T]
