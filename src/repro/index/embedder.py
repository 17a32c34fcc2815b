"""Deterministic JAX text embedder (E5 stand-in; see DESIGN.md §8.2).

Hashed unigram+bigram features -> fixed random projection -> L2 normalize.
Cosine similarity of the embeddings tracks lexical/phrasal overlap, which is
what the two-level index and evidence augmentation exploit; every method in
the benchmarks shares this embedder so comparisons stay controlled.

Batched feature->embedding projection runs under jit (it is also the math
the `l2_rank` device ranking consumes at corpus scale).
"""
from __future__ import annotations

import hashlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.tokens import words

N_FEATURES = 4096
EMBED_DIM = 256


def _hash(token: str) -> int:
    return int.from_bytes(hashlib.blake2b(token.encode(), digest_size=4).digest(), "little")


def _feature_counts(text: str) -> np.ndarray:
    ws = words(text)
    v = np.zeros((N_FEATURES,), np.float32)
    for w in ws:
        v[_hash(w) % N_FEATURES] += 1.0
    for a, b in zip(ws, ws[1:]):
        v[_hash(a + "_" + b) % N_FEATURES] += 0.5
    return v


class HashedEmbedder:
    """Deterministic tf-idf hashed embedder. `fit(texts)` learns bucket idf
    weights over a reference collection (the corpus segments), which is what
    gives document/domain separation; without fit, idf=1."""

    def __init__(self, dim: int = EMBED_DIM, seed: int = 42):
        self.dim = dim
        key = jax.random.PRNGKey(seed)
        self._proj = jax.random.normal(key, (N_FEATURES, dim), jnp.float32) / np.sqrt(dim)
        self._idf = np.ones((N_FEATURES,), np.float32)
        self._project = jax.jit(self._project_fn)

    def fit(self, texts: list[str]):
        df = np.zeros((N_FEATURES,), np.float32)
        for t in texts:
            nz = _feature_counts(t) > 0
            df += nz
        n = max(len(texts), 1)
        self._idf = np.log((n + 1.0) / (df + 1.0)).astype(np.float32) + 1.0
        return self

    def _project_fn(self, feats):
        # full float32: the TPU default would round the operands to bf16,
        # and the index would then differ between backends
        emb = jnp.dot(feats, self._proj, precision=jax.lax.Precision.HIGHEST)
        norm = jnp.linalg.norm(emb, axis=-1, keepdims=True)
        return emb / jnp.maximum(norm, 1e-6)

    def embed(self, texts: list[str], _chunk: int = 1024) -> np.ndarray:
        if not texts:
            return np.zeros((0, self.dim), np.float32)
        outs = []
        for i in range(0, len(texts), _chunk):
            feats = np.stack([_feature_counts(t) for t in texts[i:i + _chunk]])
            # (1 + log tf) * idf
            feats = np.log1p(feats) * self._idf[None, :]
            outs.append(np.asarray(self._project(jnp.asarray(feats))))
        return np.concatenate(outs) if len(outs) > 1 else outs[0]

    def embed_one(self, text: str) -> np.ndarray:
        return self.embed([text])[0]
