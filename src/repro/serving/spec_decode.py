"""Speculative decoding drafters (DESIGN.md §14).

QUEST's serving bottleneck after batching/prefix-reuse/paged-prefill is the
decode loop itself: one target-model invocation per generated token. In the
extraction workload the output is overwhelmingly text that already sits in
the prompt (the retrieved evidence segments), which is the ideal regime for
*draft/verify* decoding: a cheap drafter proposes k continuation tokens,
the target model scores all of them in ONE `verify_chunk` forward, and the
longest agreeing prefix is accepted plus one bonus token — so every verify
round emits between 1 and k+1 tokens at one target invocation, and greedy
output is byte-identical to plain decode by construction (every accepted
token equals the target's own greedy choice; the first disagreement is
replaced by it).

Two drafters, pluggable behind the engine's `spec_decode=` knob:

  PromptLookupDrafter — n-gram lookup over the request's own context
      (prompt + generated so far): match the trailing n-gram, propose the
      tokens that followed its most recent earlier occurrence. Zero model
      cost; wins whenever the model copies spans from the prompt or repeats
      itself. Among same-length matches the most recent wins, but a match
      with a longer available continuation is preferred (a rightmost match
      near the end of the sequence can only propose a truncated draft).

  DraftModelDrafter — a second, small engine-managed model (a zoo config)
      decodes the proposals. The draft keeps its own slab decode cache,
      batched over the engine's slots; after each verify round it is rolled
      back to the longest prefix of its fed tokens that the target actually
      kept (attention-family drafts only: rollback is a position reset, the
      pos-gated masks hide the rejected KV).

Drafters see the engine through a narrow protocol: `on_insert(slot, req)` /
`on_free(slot)` track slot lifecycle, `draft_round(reqs, k_eff)` returns
{slot: [token, ...]} proposals (len <= k_eff[slot]). Any object with that
shape can be passed as `spec_decode=` (tests inject adversarial drafters).
A drafter is *advisory*: wrong proposals cost wasted verify positions,
never wrong output.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import decode_step, init_decode_cache, prefill
from repro.models.cache_ops import write_slot
from repro.models.config import ModelConfig


def prompt_lookup(context: list, k: int, ngram: int = 3) -> list:
    """Propose up to `k` tokens continuing `context` by n-gram lookup.

    Tries the longest n-gram first (n = `ngram` down to 1); for a given n,
    scans matches from most recent to oldest and keeps the first one with a
    full k-token continuation, falling back to the longest continuation
    seen. Contexts shorter than the n-gram window simply try shorter
    n-grams (and return [] when nothing matches). Never proposes past the
    end of the context."""
    n_ctx = len(context)
    for n in range(min(ngram, n_ctx - 1), 0, -1):
        g = tuple(context[-n:])
        best = None
        for i in range(n_ctx - n - 1, -1, -1):
            if tuple(context[i:i + n]) == g:
                cont = context[i + n:i + n + k]
                if best is None or len(cont) > len(best):
                    best = cont
                if len(cont) == k:
                    break
        if best:
            return list(best)
    return []


class PromptLookupDrafter:
    """Model-free drafting from the request's own token context."""

    def __init__(self, *, ngram: int = 3):
        self.ngram = max(1, int(ngram))
        self.stats = {"draft_model_steps": 0}

    def on_insert(self, slot: int, req) -> None:
        pass

    def on_free(self, slot: int) -> None:
        pass

    def draft_round(self, reqs: dict, k_eff: dict) -> dict:
        out = {}
        for slot, req in reqs.items():
            k = k_eff.get(slot, 0)
            if k <= 0:
                out[slot] = []
                continue
            context = list(req.prompt) + list(req.out)
            out[slot] = prompt_lookup(context, k, self.ngram)
        return out


class DraftModelDrafter:
    """Draft-model drafting: a small second model proposes continuations.

    The draft model runs its own batched slab decode cache (one row per
    engine slot). Each round it first catches up on tokens the target fed
    that the draft has not (at most the previous round's last draft token,
    on full acceptance), then feeds the pending token and k-1 of its own
    greedy proposals to produce k draft tokens. Rows are resynchronized to
    the target's kept history by common-prefix comparison at the start of
    every round, which makes rollback self-healing across partial
    acceptance, drain/requeue, and slot reuse.
    """

    def __init__(self, cfg: ModelConfig, params, *, slots: int,
                 max_len: int, chunk_size: int = 32, mesh=None):
        if cfg.family not in ("dense", "moe"):
            raise ValueError(
                f"draft model family must be dense/moe (attention KV rollback "
                f"is a position reset); got {cfg.family!r}")
        self.cfg = cfg
        if mesh is not None:
            # mesh-aware engines (DESIGN.md §15) shard the draft model with
            # the same serving layout as the target; the draft's slab cache
            # stays small enough to leave replicated
            from repro.distributed.sharding import param_shardings
            params = jax.device_put(params,
                                    param_shardings(cfg, params, mesh,
                                                    serving=True))
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.chunk_size = max(1, int(chunk_size))
        self.cache = init_decode_cache(cfg, slots, max_len)
        self.cache["pos"] = jnp.zeros((slots,), jnp.int32)
        self._decode = jax.jit(partial(decode_step, cfg))
        # one jitted prefill; chunk_size-bucketed padding below bounds the
        # distinct input shapes (and hence traces) it ever sees
        self._prefill = jax.jit(partial(prefill, self.cfg, max_len=max_len))
        self._hist: dict = {s: [] for s in range(slots)}   # tokens fed per row
        self.stats = {"draft_model_steps": 0, "draft_prefill_tokens": 0}

    # ---------------------------------------------------------- lifecycle --

    def on_insert(self, slot: int, req) -> None:
        prompt = [int(t) % self.cfg.vocab_size for t in req.prompt]
        n = len(prompt)
        assert n < self.max_len, (
            f"prompt ({n}) exceeds draft cache max_len={self.max_len}")
        b = self.chunk_size
        bucket = min(((n + b - 1) // b) * b, self.max_len)
        toks = jnp.asarray(prompt + [0] * (bucket - n), jnp.int32)[None, :]
        _, sub = self._prefill(self.params, {"tokens": toks},
                               length=jnp.asarray(n, jnp.int32))
        self.cache = write_slot(self.cache, sub, slot)
        self._hist[slot] = prompt
        self.stats["draft_prefill_tokens"] += n
    def on_free(self, slot: int) -> None:
        self._hist[slot] = []

    # ----------------------------------------------------------- drafting --

    def draft_round(self, reqs: dict, k_eff: dict) -> dict:
        V = self.cfg.vocab_size
        feeds, props, want = {}, {}, {}
        for slot, req in reqs.items():
            # resync: the longest prefix of this row's fed tokens that is
            # still the target's kept history (rollback after rejection)
            target = ([int(t) % V for t in req.prompt] +
                      [int(t) % V for t in req.out[:-1]])
            hist = self._hist[slot]
            v = 0
            while v < len(hist) and v < len(target) and hist[v] == target[v]:
                v += 1
            self._hist[slot] = hist = target[:v]
            lag = target[v:]
            k = min(k_eff.get(slot, 0),
                    self.max_len - 1 - len(target) - 1)
            props[slot] = []
            if k <= 0:
                feeds[slot] = []
                want[slot] = 0
                continue
            pending = int(req.out[-1]) % V
            feeds[slot] = lag + [pending]
            want[slot] = k
        steps = max((len(feeds[s]) + max(want[s] - 1, 0)
                     for s in feeds), default=0)
        if steps == 0:
            return props
        # roll every participating row back to its valid fed length
        pos = np.asarray(self.cache["pos"]).copy()
        for slot in feeds:
            pos[slot] = len(self._hist[slot])
        self.cache["pos"] = jnp.asarray(pos, jnp.int32)
        for _ in range(steps):
            row_tok = np.zeros((self.slots, 1), np.int64)
            fed_now = {}
            for slot in feeds:
                if feeds[slot]:
                    tok = feeds[slot].pop(0)
                elif len(props[slot]) < want[slot] and props[slot]:
                    tok = props[slot][-1]
                else:
                    continue                     # row done: dummy zero feed
                row_tok[slot, 0] = tok
                fed_now[slot] = tok
                self._hist[slot].append(tok)
            logits, self.cache = self._decode(
                self.params, jnp.asarray(row_tok, jnp.int32), self.cache)
            self.stats["draft_model_steps"] += 1
            nxt = np.asarray(jnp.argmax(logits[:, 0, :], axis=-1))
            for slot in list(fed_now):
                if not feeds[slot] and len(props[slot]) < want[slot]:
                    props[slot].append(int(nxt[slot]))
        # drop rows' pos back to their true fed length (dummy feeds advanced
        # every row; garbage KV past pos is masked and overwritten later)
        pos = np.asarray(self.cache["pos"]).copy()
        for slot in props:
            pos[slot] = len(self._hist[slot])
        self.cache["pos"] = jnp.asarray(pos, jnp.int32)
        return props
