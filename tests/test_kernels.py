"""Per-kernel allclose vs. the pure-jnp oracles (interpret=True on CPU).

Each Pallas kernel is swept over shapes (incl. non-aligned tails where the
wrapper pads), GQA group factors, causal/non-causal, and dtypes. The
retrieval ranking (`l2_rank`, plain XLA on every backend) is held to an
exact numpy ranking, ties in index order.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.index.vector_index import l2_rank
from repro.kernels import ref
from repro.kernels.decode_attention import (decode_attention_pallas,
                                            paged_decode_attention_pallas,
                                            paged_decode_attention_ref,
                                            paged_verify_attention_pallas,
                                            paged_verify_attention_ref)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.moe_gating import moe_gating_pallas
from repro.kernels.ssm_scan import ssm_scan_pallas

KEY = jax.random.PRNGKey(0)


def rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ------------------------------------------------------- flash attention ---


@pytest.mark.parametrize("B,S,H,Hkv,D", [
    (1, 128, 2, 2, 64),
    (2, 256, 4, 2, 64),
    (1, 256, 8, 1, 128),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, S, H, Hkv, D, causal, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = rand(k1, (B, S, H, D), dtype)
    k = rand(k2, (B, S, Hkv, D), dtype)
    v = rand(k3, (B, S, Hkv, D), dtype)
    out = flash_attention_pallas(q, k, v, causal=causal, bq=64, bk=64,
                                 interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


# ------------------------------------------------------- decode attention --


@pytest.mark.parametrize("B,S,H,Hkv,D,length", [
    (2, 512, 4, 2, 64, 317),
    (1, 1024, 8, 8, 128, 1024),
    (3, 256, 2, 1, 64, 19),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(B, S, H, Hkv, D, length, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = rand(k1, (B, H, D), dtype)
    kc = rand(k2, (B, S, Hkv, D), dtype)
    vc = rand(k3, (B, S, Hkv, D), dtype)
    out = decode_attention_pallas(q, kc, vc, length, bk=128, interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, length)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("B,H,Hkv,D,P,ps,nb", [
    (2, 4, 2, 64, 16, 128, 4),
    (3, 2, 1, 128, 9, 256, 2),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention(B, H, Hkv, D, P, ps, nb, dtype):
    """The paged kernel walks K/V through a scalar-prefetched page table —
    scattered physical pages must attend identically to the gathered dense
    cache (both against the jnp gather reference and the dense kernel)."""
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    q = rand(k1, (B, H, D), dtype)
    kp = rand(k2, (P, ps, Hkv, D), dtype)
    vp = rand(k3, (P, ps, Hkv, D), dtype)
    # distinct random physical pages per row, deliberately out of order
    perm = jax.random.permutation(k4, P)[: B * nb].reshape(B, nb)
    lengths = jnp.asarray([(nb * ps * (i + 1)) // (B + 1) for i in range(B)],
                          jnp.int32)
    out = paged_decode_attention_pallas(q, kp, vp, perm, lengths, interpret=True)
    want = paged_decode_attention_ref(q, kp, vp, perm, lengths)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)
    # cross-check the reference itself against the dense-path reference
    kg = kp[perm].reshape(B, nb * ps, Hkv, D)
    vg = vp[perm].reshape(B, nb * ps, Hkv, D)
    dense = jnp.stack([ref.decode_attention_ref(q[i:i + 1], kg[i:i + 1],
                                                vg[i:i + 1], lengths[i])[0]
                       for i in range(B)])
    np.testing.assert_allclose(np.asarray(want, np.float32),
                               np.asarray(dense, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("B,H,Hkv,C,D,P,ps,nb", [
    (2, 4, 2, 5, 64, 16, 128, 4),
    (3, 2, 1, 3, 128, 9, 256, 2),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_verify_attention(B, H, Hkv, C, D, P, ps, nb, dtype):
    """Speculative-verification kernel: C candidate tokens per row attend
    the paged KV causally from per-row start positions — must match the
    gathered-dense causal reference (the batched-verify decode path of
    DESIGN.md §14)."""
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    q = rand(k1, (B, H, C, D), dtype)
    kp = rand(k2, (P, ps, Hkv, D), dtype)
    vp = rand(k3, (P, ps, Hkv, D), dtype)
    perm = jax.random.permutation(k4, P)[: B * nb].reshape(B, nb)
    # per-row starts, incl. one crossing a page boundary mid-candidates
    starts = jnp.asarray([(nb * ps * (i + 1)) // (B + 1) - C // 2
                          for i in range(B)], jnp.int32)
    out = paged_verify_attention_pallas(q, kp, vp, perm, starts, interpret=True)
    want = paged_verify_attention_ref(q, kp, vp, perm, starts)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


# --------------------------------------------------------------- topk_l2 ---


def _numpy_ranking(db, q):
    """Reference: float64 distances, stable sort (equal distances keep
    index order)."""
    d = np.sqrt(((np.asarray(q, np.float64)[:, None, :]
                  - np.asarray(db, np.float64)[None]) ** 2).sum(-1))
    idx = np.argsort(d, axis=1, kind="stable")
    return np.take_along_axis(d, idx, axis=1), idx


def _unit_rows(key, shape):
    x = np.asarray(rand(key, shape))
    return x / np.linalg.norm(x, axis=1, keepdims=True)


@pytest.mark.parametrize("N,D,M,k", [
    (512, 64, 4, 5),
    (1000, 128, 7, 10),   # non-aligned N -> rows pad to the next bucket
    (256, 32, 1, 1),
])
def test_topk_l2(N, D, M, k):
    k1, k2 = jax.random.split(KEY)
    db, q = _unit_rows(k1, (N, D)), _unit_rows(k2, (M, D))
    d, i = l2_rank(db, q, k)
    dr, ir = _numpy_ranking(db, q)
    assert d.shape == i.shape == (M, k)
    np.testing.assert_array_equal(i, ir[:, :k])
    np.testing.assert_allclose(d, dr[:, :k], atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("k", [7, None])
def test_topk_l2_tie_order(k):
    """Duplicated rows tie exactly; both the top_k and the full-ranking
    path must return them in index order, as numpy's stable sort does."""
    k1, k2 = jax.random.split(KEY)
    base = _unit_rows(k1, (75, 16))
    db = np.concatenate([base, base[::-1], base, base[:37]])   # 262 rows
    q = np.concatenate([base[[3, 40]], _unit_rows(k2, (2, 16))])
    d, i = l2_rank(db, q, k)
    dr, ir = _numpy_ranking(db, q)
    kk = len(db) if k is None else k
    np.testing.assert_array_equal(i, ir[:, :kk])
    np.testing.assert_allclose(d, dr[:, :kk], atol=1e-5, rtol=1e-5)


# -------------------------------------------------------------- ssm scan ---


@pytest.mark.parametrize("B,S,di,N", [
    (1, 64, 256, 8),
    (2, 128, 512, 16),
    (1, 96, 256, 4),     # chunk 32 divides 96
])
def test_ssm_scan(B, S, di, N):
    ks = jax.random.split(KEY, 5)
    x = rand(ks[0], (B, S, di), scale=0.5)
    dt = jax.nn.softplus(rand(ks[1], (B, S, di)) - 1.0)
    A = -jnp.exp(rand(ks[2], (di, N), scale=0.3))
    B_mat = rand(ks[3], (B, S, N), scale=0.5)
    C_mat = rand(ks[4], (B, S, N), scale=0.5)
    D = jnp.ones((di,))
    y, h = ssm_scan_pallas(x, dt, A, B_mat, C_mat, D, bd=128, chunk=32,
                           interpret=True)
    yr, hr = ref.ssm_scan_ref(x, dt, A, B_mat, C_mat, D)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=1e-4, rtol=1e-4)


# ------------------------------------------------------------ moe gating ---


@pytest.mark.parametrize("T,E,k", [(100, 8, 2), (256, 64, 6), (17, 4, 2)])
def test_moe_gating(T, E, k):
    logits = rand(KEY, (T, E), scale=2.0)
    w, i = moe_gating_pallas(logits, k, bt=64, interpret=True)
    wr, ir = ref.moe_gating_ref(logits, k)
    np.testing.assert_allclose(np.asarray(w), np.asarray(wr), atol=1e-5, rtol=1e-5)
    # same expert sets (order may tie-break differently within equal probs)
    np.testing.assert_array_equal(np.sort(np.asarray(i), 1), np.sort(np.asarray(ir), 1))
