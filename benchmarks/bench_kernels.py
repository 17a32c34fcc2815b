"""Kernel microbenchmarks (CPU XLA-path wall time + derived bandwidth).

TPU performance is covered by the roofline analysis; this harness times the
jnp reference paths that the dry-run lowers (and validates the Pallas
wrappers once in interpret mode for plumbing).
"""
from __future__ import annotations

import csv
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.index.vector_index import l2_rank_device
from repro.kernels import ref

OUT = Path(__file__).parent / "out"


def _time(fn, *args, iters=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        fn(*args).block_until_ready()
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
        leaf = out[0] if isinstance(out, tuple) else out
        leaf.block_until_ready()
    return (time.time() - t0) / iters * 1e6


def run(quick: bool = False):
    OUT.mkdir(exist_ok=True)
    key = jax.random.PRNGKey(0)
    rows = []

    B, S, H, Hkv, D = 1, 512, 8, 2, 64
    q = jax.random.normal(key, (B, S, H, D))
    k = jax.random.normal(key, (B, S, Hkv, D))
    v = jax.random.normal(key, (B, S, Hkv, D))
    fa = jax.jit(lambda q, k, v: ref.flash_attention_ref(q, k, v, causal=True))
    us = _time(fa, q, k, v)
    fl = 4 * B * S * S * H * D
    rows.append(("flash_attention_ref_512", us, f"{fl/us*1e-3:.1f}MFLOP/s/core"))

    qd = jax.random.normal(key, (4, H, D))
    kc = jax.random.normal(key, (4, 4096, Hkv, D))
    vc = jax.random.normal(key, (4, 4096, Hkv, D))
    da = jax.jit(lambda q, k, v: ref.decode_attention_ref(q, k, v, 4096))
    us = _time(da, qd, kc, vc)
    by = 2 * kc.size * 4
    rows.append(("decode_attention_ref_4k", us, f"{by/us*1e-3:.1f}MB/s/core"))

    db = jax.random.normal(key, (8192, 256))
    qq = jax.random.normal(key, (16, 256))
    us = _time(lambda d, q: l2_rank_device(d, q, db.shape[0], 10), db, qq)
    rows.append(("l2_rank_8k", us, f"{db.size*4/us*1e-3:.1f}MB/s/core"))

    from repro.models.ssm import mamba2_ssd_ref
    x = jax.random.normal(key, (1, 512, 16, 64))
    dt = jax.nn.softplus(jax.random.normal(key, (1, 512, 16)))
    A = -jnp.ones((16,))
    Bm = jax.random.normal(key, (1, 512, 64))
    Cm = jax.random.normal(key, (1, 512, 64))
    ssd = jax.jit(lambda x, dt, Bm, Cm: mamba2_ssd_ref(x, dt, A, Bm, Cm,
                                                       jnp.ones((16,)), chunk=64))
    us = _time(ssd, x, dt, Bm, Cm)
    rows.append(("mamba2_ssd_ref_512", us, "chunked-matrix-form"))

    logits = jax.random.normal(key, (4096, 64))
    mg = jax.jit(lambda l: ref.moe_gating_ref(l, 6))
    us = _time(mg, logits)
    rows.append(("moe_gating_ref_4k", us, "top6-of-64"))

    with open(OUT / "kernel_microbench.csv", "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["name", "us_per_call", "derived"])
        w.writerows(rows)
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    return rows
