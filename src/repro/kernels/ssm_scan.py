"""Pallas TPU selective scan (Mamba1 hot spot).

TPU adaptation (DESIGN.md §5): channels ride the 128-wide VPU lanes, time is
sequential *inside* the kernel with the SSM state held in VMEM scratch —
one HBM read per input element and one write per output element, no state
round-trips (the CUDA version's shared-memory prefix scan becomes a
lane-vectorized VMEM-resident recurrence). The sequence is tiled over the
sequential grid axis so the working set stays a (chunk x bd) tile.

Grid: (B, di/bd, S/chunk), state scratch (bd, N) persists across chunks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, h_ref,
            h_scr, *, chunk: int, nc: int):
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    A = a_ref[...].astype(jnp.float32)                       # (bd, N)
    Dp = d_ref[...].astype(jnp.float32)                      # (1, bd)

    def step(t, h):
        x_t = x_ref[0, t, :].astype(jnp.float32)             # (bd,)
        dt_t = dt_ref[0, t, :].astype(jnp.float32)           # (bd,)
        B_t = b_ref[0, t, :].astype(jnp.float32)             # (N,)
        C_t = c_ref[0, t, :].astype(jnp.float32)             # (N,)
        da = jnp.exp(dt_t[:, None] * A)                      # (bd, N)
        h = da * h + (dt_t * x_t)[:, None] * B_t[None, :]
        y = jnp.sum(h * C_t[None, :], axis=1) + Dp[0] * x_t
        y_ref[0, t, :] = y.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_scr[...])
    h_scr[...] = h

    @pl.when(s == nc - 1)
    def _finalize():
        h_ref[0, :, :] = h_scr[...]


@functools.partial(jax.jit, static_argnames=("bd", "chunk", "interpret"))
def ssm_scan_pallas(x, dt, A, B_mat, C_mat, D, h0=None, *, bd=256, chunk=64,
                    interpret=False):
    """Shapes as mamba1_scan_ref: x/dt (B,S,di); A (di,N); B/C (B,S,N); D (di).
    Returns (y (B,S,di), h_final (B,di,N) fp32)."""
    Bsz, S, di = x.shape
    N = A.shape[-1]
    bd = min(bd, di)
    chunk = min(chunk, S)
    assert di % bd == 0 and S % chunk == 0, (di, bd, S, chunk)
    nd, nc = di // bd, S // chunk
    assert h0 is None, "cache-seeded scan handled by the decode path"

    kernel = functools.partial(_kernel, chunk=chunk, nc=nc)
    y, h_fin = pl.pallas_call(
        kernel,
        grid=(Bsz, nd, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, bd), lambda b, d, s: (b, s, d)),   # x
            pl.BlockSpec((1, chunk, bd), lambda b, d, s: (b, s, d)),   # dt
            pl.BlockSpec((bd, N), lambda b, d, s: (d, 0)),             # A
            pl.BlockSpec((1, chunk, N), lambda b, d, s: (b, s, 0)),    # B
            pl.BlockSpec((1, chunk, N), lambda b, d, s: (b, s, 0)),    # C
            pl.BlockSpec((1, bd), lambda b, d, s: (0, d)),             # D
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, bd), lambda b, d, s: (b, s, d)),
            pl.BlockSpec((1, bd, N), lambda b, d, s: (b, d, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, S, di), x.dtype),
            jax.ShapeDtypeStruct((Bsz, di, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bd, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, A, B_mat, C_mat, D.reshape(1, di))
    return y, h_fin
