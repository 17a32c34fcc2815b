"""Jit'd public wrappers for the kernel layer.

Backend selection: "pallas" lowers the Pallas TPU kernels (interpret=True on
CPU so the same kernel body is validated in this container); "xla" runs the
mathematically identical jnp path (used by the distributed dry-run, where
Pallas-for-CPU cannot be compiled ahead-of-time). Default: xla on CPU,
pallas on TPU. No model or index path calls these wrappers yet (ROADMAP D1);
retrieval ranks with `index.vector_index.l2_rank`, plain XLA everywhere.
"""
from __future__ import annotations

import jax

from . import ref

_FORCE_BACKEND = None  # test hook


def set_backend(name):
    global _FORCE_BACKEND
    _FORCE_BACKEND = name


def backend() -> str:
    if _FORCE_BACKEND:
        return _FORCE_BACKEND
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ------------------------------------------------------ flash attention ----


def flash_attention(q, k, v, *, causal: bool = True):
    if backend() == "pallas":
        from .flash_attention import flash_attention_pallas
        return flash_attention_pallas(q, k, v, causal=causal, interpret=_interpret())
    return ref.flash_attention_ref(q, k, v, causal=causal)


def decode_attention(q, k_cache, v_cache, length):
    if backend() == "pallas":
        from .decode_attention import decode_attention_pallas
        return decode_attention_pallas(q, k_cache, v_cache, length,
                                       interpret=_interpret())
    return ref.decode_attention_ref(q, k_cache, v_cache, length)


# ------------------------------------------------------------ ssm scan -----


def ssm_scan(x, dt, A, B_mat, C_mat, D, h0=None):
    if backend() == "pallas":
        from .ssm_scan import ssm_scan_pallas
        return ssm_scan_pallas(x, dt, A, B_mat, C_mat, D, h0=h0,
                               interpret=_interpret())
    return ref.ssm_scan_ref(x, dt, A, B_mat, C_mat, D, h0=h0)


# ---------------------------------------------------------- moe gating -----


def moe_gating(logits, k: int):
    if backend() == "pallas":
        from .moe_gating import moe_gating_pallas
        return moe_gating_pallas(logits, k, interpret=_interpret())
    return ref.moe_gating_ref(logits, k)
