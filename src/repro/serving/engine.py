"""Batched serving engine with continuous batching (slot-based)
(DESIGN.md §7). Inputs are token-level `Request`s; outputs are greedy
decoded ids, byte-identical across every layout/optimization below.

Two KV layouts (DESIGN.md §10/§12):

`kv_layout="paged"` (default) — vLLM-style block layout. Length-indexed KV
lives in a fixed pool of `page_size`-token pages (`models.cache_ops.
PageAllocator`); each slot is a page table, and the decode/prefill model
code runs over views gathered through it. Prompts prefill in fixed-size
chunks (`chunk_size` tokens per jitted `prefill_chunk` call, remainder
chunk exact — jit signatures stay bounded) instead of token-at-a-time
decode steps. A request whose prompt extends a cached prefix splices the
prefix's page ids into its table — O(1) in KV bytes, ref-counted, with
copy-on-write on the partially-filled boundary page — and chunk-prefills
only the unshared suffix. Pure-state buffers (SSM conv/ssm state, enc-dec
cross KV) are not length-indexed: they stay in the per-slot state cache and
prefix entries carry the exact boundary state, so paging is correct for all
six model families, not just attention.

`kv_layout="slab"` — the PR 2 layout kept for comparison: per-slot
contiguous KV, prefix hits copy a materialized snapshot into the slot
(`expand_snapshot`/`write_slot`) and the unshared suffix prefills one token
at a time through the decode step. Full prefills bucket their jit
signatures: prompts are right-padded to the next `chunk_size` multiple and
`prefill(..., length=n)` keeps the state exact at the true length.

Shared-prefix semantics are layout-invariant: decoded outputs are identical
with the cache on or off and across layouts (tests/test_paged_kv.py);
savings are reported separately (`stats["prefix_saved_tokens"]`).

Speculative decoding (DESIGN.md §14): with `spec_decode=` on, decode runs
as draft/verify rounds — a drafter (prompt-lookup n-grams or a small draft
model, `serving/spec_decode.py`) proposes up to `spec_k` tokens per live
slot, one batched `verify_chunk` forward scores every slot's pending token
plus drafts at per-row positions, and the longest greedy-agreeing prefix
plus one bonus token is emitted. Rejected suffixes roll back exactly:
paged KV is scrubbed and speculative page refs released
(`cache_ops.truncate_pages` / `release_trailing_pages`), SSM/conv state is
restored from per-position checkpoints. Greedy output is byte-identical to
plain decode for every drafter and family (tests/test_spec_decode.py);
the economy is reported via `stats["draft_tokens"]` /
`stats["accepted_tokens"]` / `stats["decode_steps_saved"]`.

Mesh-aware serving (DESIGN.md §15): with `mesh=` set (a (data, model) mesh
from `launch/mesh.py`, CPU meshes supported for CI), the engine runs every
phase multi-device: params take the column-parallel serving layout of
`distributed/sharding.py` (no cross-device partial sums, so bf16 rounds
as on one device), the decode cache shards its slot axis over
`data` and heads/features over `model`, and the paged KV pool shards pages
replicated / heads over `model` (page tables stay host-local integers).
The jitted phases — chunked prefill, paged decode, and spec-decode verify —
thread the mesh's activation-constraint hook through the model and pin
their cache/pool outputs to explicit PartitionSpecs, so the layout is
stable across steps. Decoded rows are byte-identical to the single-device
engine (tests/test_sharded_serving.py). Data-parallel *replica* scaling on
top of one engine lives in `serving/replicas.py`.

Fault tolerance: `drain_slot` evicts a request (e.g. on a simulated worker
failure) and requeues it; the scheduler resubmits from the prompt. Retries
are bounded by `Request.max_retries` — beyond it the request fails visibly
into `engine.failed` instead of looping forever. `run()` raises
`RunTruncated` (strict default) when `max_steps` is exhausted with work
still queued/active, so callers can never mistake partial results for
complete ones.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from functools import partial
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.distributed.sharding import (cache_specs, make_constrain,
                                        param_shardings, pool_specs,
                                        to_shardings)
from repro.models import (decode_step, encode_cross_kv, init_decode_cache,
                          prefill, prefill_chunk, verify_chunk)
from repro.models.cache_ops import (PAGE_SINK, PageAllocator,
                                    PagePoolExhausted, cache_nbytes,
                                    expand_snapshot, gather_page_views,
                                    prefix_snapshot, release_trailing_pages,
                                    scatter_chunk_pages,
                                    scatter_chunk_pages_rows,
                                    scatter_token_pages, truncate_pages,
                                    write_slot)
from repro.models.config import ModelConfig
from repro.data import lm_data
from repro.obs import MetricsRegistry, StatsDict, as_tracer
from repro.obs.metrics import ENGINE_STATS
from .prefix_cache import PrefixCache
from .spec_decode import DraftModelDrafter, PromptLookupDrafter


@dataclass
class Request:
    rid: int
    prompt: list
    max_new: int = 16
    eos_id: int = lm_data.EOS
    shared_len: int = 0      # prompt[:shared_len] is shareable across requests
    max_retries: int = 3     # drain_slot evictions tolerated before failing
    tenant: str = ""         # admission-control identity (serving/frontend.py)
    priority: int = 0        # admission priority class (higher first)
    out: list = field(default_factory=list)
    done: bool = False
    submitted_s: float = 0.0
    finished_s: float = 0.0
    retries: int = 0
    error: Optional[str] = None
    # per-request speculative-decode economy (per-tenant acceptance rates)
    draft_tokens: int = 0
    accepted_tokens: int = 0
    # live-corpus provenance (DESIGN.md §17): doc_ids whose text the prompt
    # embeds, and the token offset where that content starts. A prefix-cache
    # entry is tagged with content_docs only when its boundary reaches past
    # content_start — template-only prefixes stay invalidation-immune.
    content_docs: tuple = ()
    content_start: Optional[int] = None


class RunTruncated(RuntimeError):
    """`run()` exhausted max_steps with requests still queued/active."""

    def __init__(self, msg: str, finished: dict):
        super().__init__(msg)
        self.finished = finished


# Every model phase compiles with one rounding point per bf16 op (no excess
# precision carried through a fusion, whose extent differs from one program
# shape to the next), so a mesh engine and a one-device engine decode the
# same tokens.
_jit = partial(jax.jit, compiler_options={"xla_allow_excess_precision": False})


def _jit_phase(fn, name: str, donate: tuple = ()):
    """Jit one engine phase under a stable name: the compiled program is
    `jit_<name>`, the name the device trace's XLA Modules line gives it
    (PERF.md reads `jit_prefill_chunk` and `jit_verify_round`).

    donate: argument positions whose buffers the program may reuse. The
    paged phases donate the KV pool, so the pages they dirty are written in
    place instead of into a fresh copy of the whole pool; every caller
    rebinds `alloc.pools` from the result. The state cache is never
    donated: the prefix cache keeps a B=1 prefill state by reference, and a
    verify round's rollback reads the cache it was given."""
    fn.__name__ = fn.__qualname__ = name
    return _jit(fn, donate_argnums=donate)


def _pow2_at_least(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


@jax.jit
def _restore_ckpt_rows(ssm, conv, ck_ssm, ck_conv, keeps, mask):
    """Batched SSM/conv rollback: for every row with mask[b], replace the
    state with the per-position checkpoint at keeps[b] kept tokens — one
    vectorized dispatch per verify round instead of two scatters per slot.
    ssm (L, B, ...); conv (L, B, K-1, ...); ck_ssm (L, B, C, ...);
    ck_conv (L, B, K-1+C, ...)."""
    km1 = conv.shape[2]

    def pick_ssm(row, k):                        # (L, C, ...) -> (L, ...)
        return jax.lax.dynamic_index_in_dim(row, k - 1, axis=1,
                                            keepdims=False)

    def pick_conv(row, k):                       # (L, K-1+C, ...) -> window
        return jax.lax.dynamic_slice_in_dim(row, k, km1, axis=1)

    new_ssm = jax.vmap(pick_ssm, in_axes=(1, 0), out_axes=1)(ck_ssm, keeps)
    new_conv = jax.vmap(pick_conv, in_axes=(1, 0), out_axes=1)(ck_conv, keeps)
    ms = mask.reshape((1, -1) + (1,) * (ssm.ndim - 2))
    mc = mask.reshape((1, -1) + (1,) * (conv.ndim - 2))
    return (jnp.where(ms, new_ssm.astype(ssm.dtype), ssm),
            jnp.where(mc, new_conv.astype(conv.dtype), conv))


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, *, slots: int = 4,
                 max_len: int = 256, greedy: bool = True,
                 queue_depth: Optional[int] = None,
                 prefix_cache: Union[bool, PrefixCache, None] = False,
                 prefix_min_len: int = 8,
                 kv_layout: str = "paged", page_size: int = 16,
                 num_pages: Optional[int] = None, chunk_size: int = 32,
                 spec_decode="off", spec_k: int = 4, spec_ngram: int = 3,
                 draft_model: Optional[tuple] = None, mesh=None,
                 page_allocator: Optional[PageAllocator] = None,
                 tracer=None, metrics=None):
        """queue_depth: optional admission-control bound on queued requests;
        ServedExtractor splits its batch rounds into windows of this size
        (None = unbounded).
        prefix_cache: shared-prefix KV reuse — False/None off, True for a
        default `PrefixCache()`, or a configured instance.
        prefix_min_len: shortest prefix worth snapshotting/splicing.
        kv_layout: "paged" (block/page-table KV + chunked prefill) or
        "slab" (per-slot contiguous KV, PR 2's layout).
        page_size: tokens per KV page (paged layout; must divide max_len).
        num_pages: pool capacity (default (slots+4) tables' worth + sink).
        chunk_size: prompt tokens per chunked-prefill call; also the
        bucket granularity for slab-mode prefill jit signatures.
        spec_decode: speculative decoding (DESIGN.md §14) — "off" (plain
        one-token decode steps), "prompt_lookup" (n-gram drafting over the
        request's own context), "draft" (a second small model, see
        `draft_model`), or a custom drafter instance. Greedy output is
        byte-identical across all settings.
        spec_k: draft tokens per verify round (each round emits 1..k+1).
        spec_ngram: longest n-gram the prompt-lookup drafter matches.
        draft_model: (ModelConfig, params) of the draft model, required for
        spec_decode="draft" (dense/moe family, same vocab).
        mesh: optional (data, model) jax Mesh (see `launch/mesh.py`) — run
        the engine multi-device with column-parallel params, sharded decode
        cache / paged KV pool, and mesh-constrained jitted phases (DESIGN.md
        §15). Rows stay byte-identical to the single-device engine.
        page_allocator: an existing PageAllocator to use instead of
        constructing one — `serving/replicas.py` shares a pool (and with it
        the prefix-cache page references) across engine replicas."""
        self.cfg = cfg
        self.mesh = mesh
        if mesh is not None:
            # column-parallel serving layout; a no-op when `params` already
            # carries these shardings (replica groups pre-shard once)
            params = jax.device_put(
                params, param_shardings(cfg, params, mesh, serving=True))
            self._constrain = make_constrain(mesh, slots, serving=True)
            self._constrain1 = make_constrain(mesh, 1, serving=True)  # B=1
        else:
            self._constrain = self._constrain1 = None
        self._cache_pspecs = self._pool_pspecs = None
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.greedy = greedy
        self.queue_depth = queue_depth
        if isinstance(prefix_cache, PrefixCache):   # may be empty, i.e. falsy
            self.prefix_cache: Optional[PrefixCache] = prefix_cache
        else:
            self.prefix_cache = PrefixCache() if prefix_cache else None
        self.prefix_min_len = max(1, int(prefix_min_len))
        if kv_layout not in ("paged", "slab"):
            raise ValueError(f"kv_layout must be 'paged' or 'slab', got {kv_layout!r}")
        self.kv_layout = kv_layout
        self.paged = kv_layout == "paged"
        self.page_size = max(1, int(page_size))
        self.chunk_size = max(1, int(chunk_size))
        # vlm: image tokens occupy the first cache positions of every prompt
        self._extra = cfg.n_image_tokens if cfg.family == "vlm" else 0
        self.queue: deque = deque()
        self.active: dict = {}          # slot -> Request
        self.finished: dict = {}
        self.failed: dict = {}          # rid -> Request (retry cap exceeded)
        self.cancelled: dict = {}       # rid -> Request (cancel() resolved)
        self._inserting: dict = {}      # slot -> (Request, insert coroutine)
        self.spec_k = max(1, int(spec_k))
        if isinstance(spec_decode, str):
            if spec_decode not in ("off", "prompt_lookup", "draft"):
                raise ValueError(
                    f"spec_decode must be 'off', 'prompt_lookup', 'draft' or "
                    f"a drafter instance, got {spec_decode!r}")
            if spec_decode == "prompt_lookup":
                self.drafter = PromptLookupDrafter(ngram=spec_ngram)
            elif spec_decode == "draft":
                if draft_model is None:
                    raise ValueError(
                        "spec_decode='draft' requires draft_model=(cfg, params)")
                dcfg, dparams = draft_model
                if dcfg.vocab_size != cfg.vocab_size:
                    raise ValueError(
                        f"draft vocab {dcfg.vocab_size} != target vocab "
                        f"{cfg.vocab_size}")
                self.drafter = DraftModelDrafter(dcfg, dparams, slots=slots,
                                                 max_len=max_len, mesh=mesh)
            else:
                self.drafter = None
        else:
            # custom drafter instance (tests); falsy (None/False) reads as
            # off, mirroring the prefix_cache parameter's bool convention
            self.drafter = spec_decode or None
            if self.drafter is not None and \
                    not hasattr(self.drafter, "draft_round"):
                raise ValueError(
                    f"spec_decode instance must implement the drafter "
                    f"protocol (draft_round/on_insert/on_free), got "
                    f"{spec_decode!r}")
        self.spec = self.drafter is not None
        # observability (DESIGN.md §19): engine counters live in a typed
        # MetricsRegistry behind the same dict read/write surface as the
        # old plain dict — an undeclared key is now a hard schema error.
        # One registry per engine (shared instruments would double-count
        # under replica aggregation); `tracer` spans the engine phases.
        self.tracer = as_tracer(tracer)
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.stats = StatsDict(self.metrics, "engine", ENGINE_STATS)

        # expert-layer counters (paged phases): the programs count each
        # token's top-k picks that fall on the held experts; the host keeps
        # the real tokens' share at reads it makes anyway
        moe_layers = cfg.num_layers - cfg.first_dense_layers \
            if cfg.family == "moe" and cfg.n_experts else 0
        self._assign_per_token = moe_layers * cfg.moe_top_k
        self._count_held = self.paged and moe_layers > 0
        # prefill chunks' held assignments, summed on the device
        self._held_dev = jnp.zeros((), jnp.uint32) if self._count_held \
            else None
        self._held_seen = 0

        self.cache = init_decode_cache(cfg, slots, max_len)
        self.cache["pos"] = jnp.zeros((slots,), jnp.int32)
        self._live = np.zeros((slots,), bool)
        self._tokens = jnp.zeros((slots, 1), jnp.int32)
        # start of the open host gap (`_synced`): the device has no phase
        # program queued from then until the next one is dispatched
        self._gap_t0: Optional[float] = 0.0

        def _dec(params, tokens, cache):
            # full-batch decode gets the batched constrain hook + sticky
            # cache specs; B=1 sub-cache suffix prefill (slab) the B=1 hook
            full = tokens.shape[0] == self.slots
            logits, new = decode_step(
                cfg, params, tokens, cache,
                constrain=self._constrain if full else self._constrain1)
            if full:
                new = self._with_specs(new, self._cache_pspecs)
            return logits, new
        self._decode = _jit_phase(_dec, "slab_decode")
        self._prefill_cache = {}

        def _vslab(params, toks, cache):
            logits, new, ckpts = verify_chunk(cfg, params, {"tokens": toks},
                                              cache, constrain=self._constrain)
            return logits, self._with_specs(new, self._cache_pspecs), ckpts
        self._verify_slab = _jit_phase(_vslab, "slab_verify")
        self._verify_fns: dict = {}

        if self.paged:
            assert max_len % self.page_size == 0, (
                f"max_len={max_len} must be a multiple of page_size={page_size}")
            self.pages_per_slot = max_len // self.page_size
            if page_allocator is not None:
                assert page_allocator.page_size == self.page_size, (
                    f"shared allocator page_size={page_allocator.page_size} "
                    f"!= engine page_size={self.page_size}")
                self.alloc = page_allocator   # shared pool: replica groups
            else:
                if num_pages is None:
                    num_pages = (slots + 4) * self.pages_per_slot + 1
                self.alloc = PageAllocator(cfg, num_pages, self.page_size)
                if mesh is not None:
                    self.alloc.shard_pools(mesh)
            for k in self.alloc.pools:   # length-indexed KV lives in the pool
                del self.cache[k]
            self.slot_pages: list = [[] for _ in range(slots)]
            self._pos_h = np.zeros((slots,), np.int64)   # host mirror of pos
            self._chunk_fns: dict = {}
            self._paged_decode = _jit_phase(self._make_paged_decode(),
                                            "paged_decode", donate=(3,))
            self._cross_kv = None                         # encdec, computed once

        if mesh is not None:
            # sticky layouts for the state that persists across steps: the
            # jitted phases re-pin their cache/pool outputs to these specs
            self._cache_pspecs = cache_specs(cfg, self.cache, mesh, slots)
            self.cache = jax.device_put(
                self.cache, to_shardings(mesh, self._cache_pspecs))
            if self.paged:
                self._pool_pspecs = pool_specs(self.alloc.pools, mesh)

    def _with_specs(self, tree: dict, pspecs) -> dict:
        """Pin a cache/pool pytree's leaves to the engine's mesh specs
        (jit-traceable `with_sharding_constraint`); identity off-mesh."""
        if self.mesh is None or pspecs is None:
            return tree
        out = dict(tree)
        for k, spec in pspecs.items():
            if k in out:
                out[k] = jax.lax.with_sharding_constraint(
                    out[k], NamedSharding(self.mesh, spec))
        return out

    # ------------------------------------------------------------ intake --

    def submit(self, req: Request):
        if self.queue_depth is not None and len(self.queue) >= self.queue_depth:
            raise RuntimeError(
                f"serving queue full ({len(self.queue)} >= {self.queue_depth})")
        req.submitted_s = time.time()
        self.queue.append(req)

    def submit_many(self, reqs):
        """All-or-nothing admission: never leaves a batch half-enqueued."""
        reqs = list(reqs)
        if self.queue_depth is not None and \
                len(self.queue) + len(reqs) > self.queue_depth:
            raise RuntimeError(
                f"serving queue full ({len(self.queue)} + {len(reqs)} > "
                f"{self.queue_depth})")
        for req in reqs:
            req.submitted_s = time.time()
            self.queue.append(req)

    # --------------------------------------------------- slab-mode prefill --

    def _bucket_len(self, n: int) -> int:
        """Next chunk_size multiple — bounds distinct prefill jit signatures
        (each distinct prompt length no longer triggers a fresh compile).
        Capped so padding never pushes text + image/frame tokens past the
        cache bound a legal prompt still fits in."""
        b = self.chunk_size
        return min(((n + b - 1) // b) * b, self.max_len - self._extra)

    def _prefill_fn(self, bucket: int):
        if bucket not in self._prefill_cache:
            cfg, max_len, constrain = self.cfg, self.max_len, self._constrain1

            def fn(params, batch, length):
                return prefill(cfg, params, batch, max_len=max_len,
                               length=length, constrain=constrain)
            self._prefill_cache[bucket] = _jit_phase(fn, "slab_prefill")
        return self._prefill_cache[bucket]

    def _prefill_sub(self, tokens: list):
        """Exact-state prefill of `tokens` into a B=1 sub-cache, padded to a
        bucketed length (one jit signature per bucket; `length` keeps the
        logits, cache position and SSM state exact at the true length).
        Returns (last-position logits, sub-cache)."""
        n = len(tokens)
        bucket = self._bucket_len(n)
        toks = jnp.asarray(list(tokens) + [0] * (bucket - n), jnp.int32)[None, :]
        batch = {"tokens": toks}
        if self.cfg.family == "encdec":
            batch["frames"] = jnp.zeros((1, self.cfg.encoder_seq, self.cfg.d_model),
                                        jnp.dtype(self.cfg.dtype))
        if self.cfg.family == "vlm":
            from repro.models.model import VISION_DIM
            batch["image_embeds"] = jnp.zeros((1, self.cfg.n_image_tokens, VISION_DIM),
                                              jnp.float32)
        self.stats["prefill_invocations"] += 1
        # attention-FLOPs proxy: KV positions computed against (S x S matrix)
        self.stats["prefill_ctx_positions"] += (self._extra + bucket) ** 2
        out = self._prefill_fn(bucket)(self.params, batch,
                                       length=jnp.asarray(n, jnp.int32))
        self._dispatched()
        return out

    def _insert_slab_co(self, slot: int, req: Request):
        """Coroutine form of the slab-layout insert: yields between prefill
        units (one bucketed prefill call, or one exact decode step per
        unshared-suffix token — the same recurrence decode uses, so SSM/conv
        state stays correct). Driven to exhaustion it computes exactly what
        the old blocking `_insert_slab` did."""
        prompt = req.prompt
        sub, prefix_len, did_work = None, 0, False
        with self.tracer.span("engine.admit", kind="engine", level=2):
            entry = None if self.prefix_cache is None else \
                self.prefix_cache.match(prompt)
            if entry is not None and len(entry.tokens) >= self.prefix_min_len:
                prefix_len = len(entry.tokens)
                sub = expand_snapshot(entry.cache, self.max_len)
                self.stats["prefix_hits"] += 1
                self.stats["prefix_saved_tokens"] += prefix_len
                self.tracer.instant("engine.prefix_hit", kind="engine",
                                    level=2, saved=prefix_len)
            elif self.prefix_cache is not None:
                # first request of a prefix group: prefill the shared prefix
                # exactly (state-correct snapshot boundary), then continue
                boundary = min(int(req.shared_len), len(prompt) - 1)
                if boundary >= self.prefix_min_len:
                    _, sub = self._prefill_sub(prompt[:boundary])
                    did_work = True
                    self.stats["prefill_tokens"] += boundary
                    self.prefix_cache.insert(
                        prompt[:boundary],
                        prefix_snapshot(sub, self._extra + boundary),
                        doc_ids=self._entry_docs(req, boundary))
                    self.stats["prefix_inserts"] += 1
                    prefix_len = boundary
        if sub is None:
            logits, sub = self._prefill_sub(prompt)
            self.stats["prefill_tokens"] += len(prompt)
        else:
            logits = None
            for t in prompt[prefix_len:]:
                if did_work:
                    yield               # cooperative point between tokens
                did_work = True
                logits, sub = self._decode(self.params,
                                           jnp.asarray([[t]], jnp.int32), sub)
                self._dispatched()
                self.stats["prefill_invocations"] += 1
                # each token-step attends the full max_len KV buffer
                self.stats["prefill_ctx_positions"] += self.max_len
            self.stats["prefill_tokens"] += len(prompt) - prefix_len
        self.cache = write_slot(self.cache, sub, slot)
        return logits

    # -------------------------------------------------- paged-mode prefill --

    def _init_state_sub(self) -> dict:
        """Fresh B=1 pure-state sub-cache (pos + conv/ssm/cross buffers)."""
        sub = {}
        for k, a in self.cache.items():
            sub[k] = jnp.zeros((), jnp.int32) if k == "pos" else \
                jnp.zeros_like(a[:, :1])
        if self.cfg.family == "encdec":
            if self._cross_kv is None:
                frames = jnp.zeros((1, self.cfg.encoder_seq, self.cfg.d_model),
                                   jnp.dtype(self.cfg.dtype))
                ck, cv = encode_cross_kv(self.cfg, self.params, frames)
                self._cross_kv = (ck.astype(self.cache["ck"].dtype),
                                  cv.astype(self.cache["cv"].dtype))
            sub["ck"], sub["cv"] = self._cross_kv
        return sub

    def _make_paged_decode(self):
        cfg, ps = self.cfg, self.page_size

        def step(params, tokens, state, pools, table, write_ids):
            dense = dict(state)
            dense.update(gather_page_views(pools, table))
            logits, new = decode_step(cfg, params, tokens, dense,
                                      constrain=self._constrain,
                                      count_held=self._count_held)
            new_state = {k: new[k] for k in state}
            if pools:
                starts = (state["pos"] // ps) * ps
                pools = scatter_token_pages(pools, new, write_ids, starts, ps)
            out = (logits, self._with_specs(new_state, self._cache_pspecs),
                   self._with_specs(pools, self._pool_pspecs))
            return out + ((new["moe_held"],) if self._count_held else ())
        return step

    def _chunk_fn(self, n_ctx: int, nb: int, with_images: bool):
        key = (n_ctx, nb, with_images)
        if key not in self._chunk_fns:
            cfg, ps = self.cfg, self.page_size
            has_pool = bool(self.alloc.pools)

            def fn(params, state, pools, ctx_ids, tokens, length, write_ids,
                   b0, *held):
                batch = {"tokens": tokens}
                if with_images:
                    from repro.models.model import VISION_DIM
                    batch["image_embeds"] = jnp.zeros(
                        (1, cfg.n_image_tokens, VISION_DIM), jnp.float32)
                dense = dict(state)
                if has_pool:
                    dense.update(gather_page_views(pools, ctx_ids[None, :]))
                logits, new = prefill_chunk(cfg, params, batch, dense,
                                            length=length,
                                            constrain=self._constrain1,
                                            count_held=bool(held))
                new_state = {k: new[k] for k in state}
                if has_pool:
                    pools = scatter_chunk_pages(pools, new, write_ids, b0, ps, nb)
                out = (logits, new_state,
                       self._with_specs(pools, self._pool_pspecs))
                if held:        # add the real tokens' held assignments
                    real = jnp.arange(tokens.shape[1]) < length
                    n = jnp.where(real, new["moe_held"][0], 0).sum()
                    out += (held[0] + n.astype(jnp.uint32),)
                return out
            self._chunk_fns[key] = _jit_phase(fn, "prefill_chunk",
                                              donate=(2,))
        return self._chunk_fns[key]

    def _ensure_pages(self, n: int, acquired: list) -> list:
        """Allocate n pages, evicting LRU prefix entries under pool pressure
        (pinned entries — pages shared with live slots — free nothing and the
        loop moves on to the next victim). Newly allocated ids are appended
        to `acquired`; on hard exhaustion the caller rolls that list back."""
        while True:
            try:
                ids = self.alloc.alloc(n)
                acquired.extend(ids)
                return ids
            except PagePoolExhausted:
                if self.prefix_cache is not None and \
                        self.prefix_cache.pop_lru() is not None:
                    continue
                raise

    def _cow_page(self, src: int, acquired: list) -> int:
        """copy_page with the same evict-LRU-under-pressure behaviour as
        `_ensure_pages`. `src` must be retained by the caller so a victim
        eviction cannot free it mid-copy."""
        while True:
            try:
                dst = self.alloc.copy_page(src)
                acquired.append(dst)
                return dst
            except PagePoolExhausted:
                if self.prefix_cache is not None and \
                        self.prefix_cache.pop_lru() is not None:
                    continue
                raise

    def _chunked_prefill_co(self, slot: int, state: dict, tokens: list,
                            lpos: int, *, first: bool = True):
        """Feed `tokens` through fixed-size prefill chunks, yielding between
        chunks so the caller can interleave decode of live slots with this
        insert's prefill. Every chunk is padded to `chunk_size` and carries
        its true length traced, so one jit signature (per pow2-bucketed
        context width) serves every prompt length and offset. KV is written
        straight into the slot's pages through a context view gathered over
        the page table. `first=False` yields before the first chunk too
        (continuation of an insert that already did a prefill unit).
        Returns (last-chunk logits, state, new logical position, first)."""
        cs, ps = self.chunk_size, self.page_size
        pages = self.slot_pages[slot]
        has_pool = bool(self.alloc.pools)
        logits, i, n = None, 0, len(tokens)
        while i < n:
            if not first:
                yield               # cooperative point between chunks
            first = False
            true_clen = min(cs, n - i)
            with self.tracer.span("engine.prefill_chunk", kind="engine",
                                  level=2, tokens=true_clen):
                with_images = self._extra > 0 and lpos == 0
                extra = self._extra if with_images else 0
                llen_pad = cs + extra     # positions the padded chunk touches
                if has_pool:
                    nb = (llen_pad + ps - 2) // ps + 1 if ps > 1 else llen_pad
                    need = -(-(lpos + llen_pad) // ps)
                    n_ctx = _pow2_at_least(max(need, nb))
                    b0 = min(lpos // ps, n_ctx - nb)
                    ctx = [pages[b] if b < len(pages) else PAGE_SINK
                           for b in range(n_ctx)]
                    wids = [pages[b] if b < len(pages) else PAGE_SINK
                            for b in range(b0, b0 + nb)]
                else:
                    nb = n_ctx = b0 = 0
                    ctx, wids = [], []
                chunk = list(tokens[i:i + true_clen]) + [0] * (cs - true_clen)
                fn = self._chunk_fn(n_ctx, nb, with_images)
                held = (self._held_dev,) if self._count_held else ()
                logits, state, self.alloc.pools, *held = fn(
                    self.params, state, self.alloc.pools,
                    jnp.asarray(ctx, jnp.int32),
                    jnp.asarray(chunk, jnp.int32)[None, :],
                    jnp.asarray(true_clen, jnp.int32),
                    jnp.asarray(wids, jnp.int32), jnp.asarray(b0, jnp.int32),
                    *held)
                self._dispatched()
                if held:
                    self._held_dev = held[0]
                    self.stats["routed_assignments"] += \
                        self._assign_per_token * true_clen
            self.stats["prefill_invocations"] += 1
            self.stats["prefill_chunks"] += 1
            self.stats["prefill_ctx_positions"] += \
                llen_pad * (n_ctx * ps if has_pool else llen_pad)
            i += true_clen
            lpos += true_clen + extra
        return logits, state, lpos, first

    @staticmethod
    def _entry_docs(req: Request, boundary: int) -> tuple:
        """Doc provenance for a prefix entry at `boundary` tokens: the
        request's content docs iff the boundary reaches into the content
        span — a template-only prefix embeds no document text and must
        survive that document's mutation."""
        if (req.content_docs and req.content_start is not None
                and boundary > req.content_start):
            return tuple(req.content_docs)
        return ()

    def _snapshot_prefix_paged(self, slot: int, prefix: list, state: dict,
                               req: Optional[Request] = None):
        """Store a prefix entry as *page references*: full pages shared by
        reference (ref-counted), the partially-filled boundary page copied
        once so the slot can keep writing into its own copy (CoW)."""
        lp = self._extra + len(prefix)
        pages = self.slot_pages[slot]
        full = lp // self.page_size
        entry_pages = list(pages[:full])
        self.alloc.retain(entry_pages)
        tail = None
        if lp % self.page_size and full < len(pages):
            try:
                tail = self._cow_page(pages[full], [])
            except PagePoolExhausted:
                # caching this prefix is an optimization, not a requirement:
                # under hard pool pressure skip the snapshot, keep serving
                self.alloc.release(entry_pages)
                return
            self.stats["cow_copies"] += 1
        snap = dict(state)
        nbytes = ((len(entry_pages) + (1 if tail is not None else 0))
                  * self.alloc.page_nbytes + cache_nbytes(snap))
        alloc, ids = self.alloc, entry_pages + ([tail] if tail is not None else [])
        self.prefix_cache.insert(prefix, snap, pages=entry_pages,
                                 tail_page=tail, nbytes=nbytes,
                                 release=(lambda: alloc.release(ids)),
                                 doc_ids=(self._entry_docs(req, len(prefix))
                                          if req is not None else ()))
        self.stats["prefix_inserts"] += 1

    def _insert_paged_co(self, slot: int, req: Request):
        """Coroutine form of the paged insert. Pages are acquired all at
        once *before the first yield* (all-or-nothing: PagePoolExhausted
        raises out of the first advance with every acquired ref rolled
        back), then the prompt chunk-prefills with a yield between chunks.
        From the first yield on, `slot_pages[slot]` owns every page ref, so
        cancelling the coroutine mid-insert cleans up via
        `_free_slot_pages(slot)` alone."""
        prompt = req.prompt
        plen = len(prompt)
        total = self._extra + plen
        ps = self.page_size
        # Positions ever written: prompt + every fed generated token. With
        # speculation on, verify rounds grow the table lazily (and roll a
        # rejected suffix's pages back), so insert covers the prompt only.
        cap = min(total if self.spec else total + req.max_new, self.max_len)
        blocks = -(-cap // ps) if self.alloc.pools else 0
        acquired: list = []
        state, prefix_len, pages = None, 0, []
        with self.tracer.span("engine.admit", kind="engine", level=2):
            try:
                entry = None if self.prefix_cache is None else \
                    self.prefix_cache.match(prompt)
                if entry is not None and len(entry.tokens) >= self.prefix_min_len:
                    # O(1) splice: share the full pages, CoW the boundary page
                    prefix_len = len(entry.tokens)
                    pages = list(entry.pages)
                    self.alloc.retain(pages)
                    acquired.extend(pages)
                    if entry.tail_page is not None:
                        tail_src = entry.tail_page
                        self.alloc.retain([tail_src])   # survive a victim evict
                        try:
                            pages.append(self._cow_page(tail_src, acquired))
                        finally:
                            self.alloc.release([tail_src])
                        self.stats["cow_copies"] += 1
                    state = dict(entry.cache)
                    self.stats["prefix_hits"] += 1
                    self.stats["prefix_saved_tokens"] += prefix_len
                    self.tracer.instant("engine.prefix_hit", kind="engine",
                                        level=2, saved=prefix_len)
                if blocks > len(pages):
                    pages = pages + self._ensure_pages(blocks - len(pages),
                                                       acquired)
            except PagePoolExhausted:
                if acquired:                # roll back the splice/CoW refs
                    self.alloc.release(acquired)
                raise
            self.slot_pages[slot] = pages
            hit = state is not None
            if not hit:
                state = self._init_state_sub()
        if not hit:
            boundary = 0 if self.prefix_cache is None else \
                min(int(req.shared_len), plen - 1)
            if boundary >= self.prefix_min_len:
                _, state, lpos, first = yield from self._chunked_prefill_co(
                    slot, state, prompt[:boundary], 0)
                self._snapshot_prefix_paged(slot, prompt[:boundary], state,
                                            req=req)
                logits, state, lpos, first = yield from self._chunked_prefill_co(
                    slot, state, prompt[boundary:], lpos, first=first)
            else:
                logits, state, lpos, _ = yield from self._chunked_prefill_co(
                    slot, state, prompt, 0)
            self.stats["prefill_tokens"] += plen
        else:
            logits, state, lpos, _ = yield from self._chunked_prefill_co(
                slot, state, prompt[prefix_len:], self._extra + prefix_len)
            self.stats["prefill_tokens"] += plen - prefix_len
        self.cache = write_slot(self.cache, state, slot)
        self._pos_h[slot] = lpos
        return logits

    def _free_slot_pages(self, slot: int):
        if self.paged and self.slot_pages[slot]:
            self.alloc.release(self.slot_pages[slot])
            self.slot_pages[slot] = []

    def _page_table(self, width: int):
        """Page table truncated to the live rows' block high-water mark
        (pow2-bucketed by the caller): decode gathers — and attends — only
        the blocks actually in use instead of the full max_len slab."""
        tbl = np.full((self.slots, width), PAGE_SINK, np.int32)
        for s, pages in enumerate(self.slot_pages):
            if self._live[s]:
                tbl[s, :min(len(pages), width)] = pages[:width]
        return jnp.asarray(tbl)

    # ----------------------------------------------------------- prefill --

    def _insert_co(self, slot: int, req: Request):
        """Coroutine insert: run `req`'s (possibly chunked) prefill into
        `slot`, yielding between prefill units so `step()` can interleave
        decode of already-live slots with admission prefill — that
        interleaving is what bounds time-to-first-token for running
        requests (and p99 time-to-first-row upstream) under bursty intake.
        The slot goes live only on completion; mid-insert it is reserved
        via `self._inserting`. Driving the coroutine to exhaustion without
        observing the yields is exactly the old blocking insert."""
        prompt = req.prompt
        assert self._extra + len(prompt) <= self.max_len, (
            f"prompt ({len(prompt)} + {self._extra} image/frame tokens) "
            f"exceeds cache max_len={self.max_len}")
        co = (self._insert_paged_co if self.paged else self._insert_slab_co)
        logits = yield from co(slot, req)
        with self.tracer.span("engine.first_token", kind="engine", level=2):
            t0 = time.perf_counter()
            nxt = int(jnp.argmax(logits[0, -1]))
            if self._count_held:        # done with the chunk just read
                held = int(np.asarray(self._held_dev))
                self.stats["held_assignments"] += \
                    (held - self._held_seen) % (1 << 32)
                self._held_seen = held
            self._synced(t0)
            if not req.retries:         # a requeued request counted already
                self.stats["first_tokens"] += 1
                self.stats["ttft_s"] += time.time() - req.submitted_s
            self._tokens = self._tokens.at[slot, 0].set(nxt)
            req.out.append(nxt)
            self.active[slot] = req
            self._live[slot] = True
            if self.spec:
                self.drafter.on_insert(slot, req)
            self._note_kv_bytes()

    def _synced(self, t0: float, reads: int = 1) -> None:
        """Count `reads` host reads of device values, begun at `t0`
        (perf_counter) and just returned. Each blocks the host until the
        device has produced the value, which leaves the device with no
        phase program queued: a host gap opens, which the next phase
        dispatch closes (`_dispatched`)."""
        now = time.perf_counter()
        self.stats["host_syncs"] += reads
        self.stats["sync_wait_s"] += now - t0
        if self._gap_t0 is not None:    # the wait itself is not host work
            self.stats["host_gap_s"] += t0 - self._gap_t0
        self._gap_t0 = now

    def _dispatched(self) -> None:
        """A phase program was just dispatched: the host gap since the last
        read (its Python, input copies and dispatch) ends here."""
        if self._gap_t0 is not None:
            self.stats["host_gap_s"] += time.perf_counter() - self._gap_t0
            self._gap_t0 = None

    def _insert(self, slot: int, req: Request):
        """Blocking insert (legacy API, kept for tests/direct callers):
        drain the insert coroutine in one go."""
        for _ in self._insert_co(slot, req):
            pass

    def _note_kv_bytes(self):
        used = cache_nbytes(self.cache)
        if self.paged:
            used += self.alloc.nbytes_in_use
        elif self.prefix_cache is not None:
            used += self.prefix_cache.nbytes
        self.stats["kv_bytes_peak"] = max(self.stats["kv_bytes_peak"], used)

    # ------------------------------------------------------------- decode --

    def _finish(self, slot: int, req: Request):
        req.done = True
        req.finished_s = time.time()
        self.finished[req.rid] = req
        del self.active[slot]
        self._live[slot] = False
        self._free_slot_pages(slot)
        if self.spec:
            self.drafter.on_free(slot)

    def _step(self):
        if self.paged:
            write_ids = np.full((self.slots,), PAGE_SINK, np.int32)
            maxb = 1
            for s in range(self.slots):
                if self._live[s]:
                    maxb = max(maxb, len(self.slot_pages[s]))
                    b = int(self._pos_h[s]) // self.page_size
                    if b < len(self.slot_pages[s]):
                        write_ids[s] = self.slot_pages[s][b]
            width = min(_pow2_at_least(maxb), self.pages_per_slot)
            logits, self.cache, self.alloc.pools, *held = self._paged_decode(
                self.params, self._tokens, self.cache, self.alloc.pools,
                self._page_table(width), jnp.asarray(write_ids))
            self._pos_h += 1
        else:
            held = []
            logits, self.cache = self._decode(self.params, self._tokens, self.cache)
        self._dispatched()
        self.stats["decode_steps"] += 1
        self.stats["decode_slot_steps"] += len(self.active)
        self.stats["max_live"] = max(self.stats["max_live"], len(self.active))
        t0 = time.perf_counter()
        nxt = np.asarray(jnp.argmax(logits[:, 0, :], axis=-1))
        pos = np.asarray(self.cache["pos"])
        if held:
            self._count_round(np.asarray(held[0]),
                              {s: 1 for s in self.active})
        self._synced(t0, 2)
        for slot, req in list(self.active.items()):
            tok = int(nxt[slot])
            req.out.append(tok)
            full = int(pos[slot]) >= self.max_len - 1
            if tok == req.eos_id or len(req.out) >= req.max_new or full:
                self._finish(slot, req)
        self._tokens = jnp.asarray(nxt[:, None], jnp.int32)

    # ------------------------------------------------ speculative decode --

    def _verify_fn(self, n_ctx: int):
        """Jitted batched verify round for the paged layout: gather every
        live row's page-table context, run `verify_chunk` over all slots at
        once (per-row positions), scatter the dirtied blocks back. One jit
        signature per pow2-bucketed context width, like decode."""
        if n_ctx not in self._verify_fns:
            cfg, ps = self.cfg, self.page_size
            C = self.spec_k + 1
            nb = (C + ps - 2) // ps + 1 if ps > 1 else C
            has_pool = bool(self.alloc.pools)

            def fn(params, state, pools, ctx_tab, toks, wtabs, b0s):
                dense = dict(state)
                if has_pool:
                    dense.update(gather_page_views(pools, ctx_tab))
                logits, new, ckpts = verify_chunk(cfg, params,
                                                  {"tokens": toks}, dense,
                                                  constrain=self._constrain,
                                                  count_held=self._count_held)
                new_state = {k: new[k] for k in state}
                if has_pool:
                    pools = scatter_chunk_pages_rows(pools, new, wtabs, b0s,
                                                     ps, nb)
                out = (logits, self._with_specs(new_state, self._cache_pspecs),
                       self._with_specs(pools, self._pool_pspecs), ckpts)
                return out + ((new["moe_held"],) if self._count_held else ())
            self._verify_fns[n_ctx] = (
                _jit_phase(fn, "verify_round", donate=(2,)), nb)
        return self._verify_fns[n_ctx]

    def _spec_grow_pages(self, slot: int, upto: int) -> int:
        """Lazily extend a slot's page table to cover `upto` positions for
        this verify round (evicting LRU prefix entries under pressure).
        Returns the number of positions that actually fit — under hard pool
        exhaustion the round is clamped to the current allocation instead of
        failing, as long as at least the pending token fits."""
        ps = self.page_size
        pages = self.slot_pages[slot]
        need = min(-(-upto // ps), self.pages_per_slot)
        if need > len(pages):
            try:
                pages += self._ensure_pages(need - len(pages), [])
            except PagePoolExhausted:
                if len(pages) * ps <= int(self._pos_h[slot]):
                    raise               # not even the pending token fits
        return min(upto, len(pages) * ps, self.max_len)

    def _spec_clamp_drafts(self, live, pos_h, drafts):
        """Clamp each live slot's drafts to its page capacity, growing
        tables lazily. A slot whose *pending token* no longer fits (pool
        pinned by other live slots, prefix LRU drained) is evicted back to
        the queue via `drain_slot` — the engine's fail-visibly path, with
        retries bounded by `Request.max_retries` — freeing its pages so the
        other slots (and, later, the requeued request) can proceed.
        Returns the live list minus any drained slots."""
        kept = []
        for s in live:
            if self.alloc.pools:
                try:
                    fit = self._spec_grow_pages(s, int(pos_h[s]) + 1 +
                                                len(drafts[s]))
                except PagePoolExhausted:
                    self.drain_slot(s)
                    continue
                drafts[s] = drafts[s][: max(fit - int(pos_h[s]) - 1, 0)]
            kept.append(s)
        return kept

    def _spec_step(self):
        """One speculative round (replaces `_step` when `spec_decode` is
        on): draft up to k tokens per live slot, verify pending+drafts for
        every slot in ONE batched `verify_chunk` forward, emit the longest
        agreeing prefix plus the target's own next token, then roll rejected
        suffixes back — position truncation + page scrub/ref-release for
        attention KV, per-position state checkpoints for SSM/conv state —
        so the engine state is exactly what plain decode would have built."""
        with self.tracer.span("engine.draft", kind="engine", level=2):
            live, pos_h, drafts = self._spec_drafts()
            if live:
                toks = np.zeros((self.slots, self.spec_k + 1), np.int64)
                for s in live:
                    row = [self.active[s].out[-1]] + drafts[s]
                    toks[s, :len(row)] = row
                if self.paged:
                    fn, ctx, wtabs, b0s = self._verify_inputs(live, pos_h)
        if not live:
            return                       # all slots drained; run() reinserts
        if self.paged:
            logits, new_state, self.alloc.pools, ckpts, *held = fn(
                self.params, self.cache, self.alloc.pools, ctx,
                jnp.asarray(toks, jnp.int32), wtabs, b0s)
            cache = dict(self.cache)
            cache.update(new_state)
        else:
            held = []
            logits, cache, ckpts = self._verify_slab(
                self.params, jnp.asarray(toks, jnp.int32), self.cache)
            cache = dict(cache)
        self._dispatched()

        self.stats["decode_steps"] += 1
        self.stats["spec_rounds"] += 1
        self.stats["decode_slot_steps"] += len(live)
        self.stats["max_live"] = max(self.stats["max_live"], len(live))
        with self.tracer.span("engine.accept", kind="engine", level=2):
            self._spec_accept(live, pos_h, drafts, logits, cache, ckpts,
                              held[0] if held else None)

    def _spec_drafts(self):
        """The draft half of a verify round: each live slot's drafts,
        clamped to what its page table can grow to. Returns (live slots,
        host positions, drafts by slot)."""
        live = [s for s in range(self.slots) if self._live[s]]
        if self.paged:
            pos_h = self._pos_h.astype(np.int64).copy()
        else:
            t0 = time.perf_counter()
            pos_h = np.asarray(self.cache["pos"]).astype(np.int64).copy()
            self._synced(t0)
        reqs = {s: self.active[s] for s in live}
        k_eff = {}
        for s in live:
            req, p0 = self.active[s], int(pos_h[s])
            k_eff[s] = max(0, min(self.spec_k,
                                  req.max_new - len(req.out) - 1,
                                  self.max_len - 1 - p0))
        drafts = self.drafter.draft_round(reqs, k_eff)
        for s in live:
            drafts[s] = list(drafts.get(s) or [])[: k_eff[s]]
        if self.paged:
            live = self._spec_clamp_drafts(live, pos_h, drafts)
        return live, pos_h, drafts

    def _verify_inputs(self, live, pos_h):
        """The paged verify program for this round's context width and its
        page-table inputs on the device: (fn, context table, write tables,
        block starts)."""
        C, ps = self.spec_k + 1, self.page_size
        nb_probe = (C + ps - 2) // ps + 1 if ps > 1 else C
        need_ctx = 1
        for s in live:
            p0 = int(pos_h[s])
            need_ctx = max(need_ctx, -(-(p0 + C) // ps), p0 // ps + nb_probe)
        n_ctx = _pow2_at_least(need_ctx)
        fn, nb = self._verify_fn(n_ctx)
        ctx = np.full((self.slots, n_ctx), PAGE_SINK, np.int32)
        wtabs = np.full((self.slots, nb), PAGE_SINK, np.int32)
        b0s = np.zeros((self.slots,), np.int32)
        for s in live:
            pages = self.slot_pages[s]
            ctx[s, :min(len(pages), n_ctx)] = pages[:n_ctx]
            b0 = min(int(pos_h[s]) // ps, n_ctx - nb)
            b0s[s] = b0
            for j in range(nb):
                b = b0 + j
                if b < len(pages):
                    wtabs[s, j] = pages[b]
        return fn, jnp.asarray(ctx), jnp.asarray(wtabs), jnp.asarray(b0s)

    def _spec_accept(self, live, pos_h, drafts, logits, cache, ckpts,
                     held=None):
        """The accept half of a verify round: read the target's greedy
        tokens back, emit each slot's agreeing prefix plus one, and roll
        the rejected suffixes back. `held`: the round's held assignments per
        position (slots, C), where the engine counts them."""
        t0 = time.perf_counter()
        Y = np.asarray(jnp.argmax(logits, axis=-1))          # (slots, C)
        nxt = np.asarray(self._tokens[:, 0]).copy()
        if held is not None:
            self._count_round(np.asarray(held),
                              {s: 1 + len(drafts[s]) for s in live})
        self._synced(t0, 2)
        new_pos = pos_h.copy()
        keeps = np.ones((self.slots,), np.int32)
        restore = np.zeros((self.slots,), bool)
        for s in live:
            req, d, p0 = self.active[s], drafts[s], int(pos_h[s])
            m = 0
            while m < len(d) and int(Y[s, m]) == d[m]:
                m += 1
            emitted = d[:m] + [int(Y[s, m])]
            done, n_app = False, 0
            for i, t in enumerate(emitted):
                req.out.append(t)
                n_app = i + 1
                if t == req.eos_id or len(req.out) >= req.max_new or \
                        p0 + i + 1 >= self.max_len - 1:
                    done = True
                    break
            keep = n_app
            self.stats["draft_tokens"] += len(d)
            # count only accepted tokens actually emitted: when EOS/max_new/
            # max_len truncates mid-prefix, the tail never reached the output
            self.stats["accepted_tokens"] += min(m, n_app)
            req.draft_tokens += len(d)
            req.accepted_tokens += min(m, n_app)
            self.stats["decode_steps_saved"] += n_app - 1
            if not done and "ssm" in ckpts:
                keeps[s] = keep                  # batched restore below
                restore[s] = True
            new_pos[s] = p0 + keep
            if done:
                self._finish(s, req)
            else:
                nxt[s] = emitted[-1]
                if self.paged and self.alloc.pools:
                    # page-truncate + ref-release the rejected suffix
                    pages = self.slot_pages[s]
                    end = min(p0 + 1 + len(d),
                              len(pages) * self.page_size)
                    if p0 + keep < end:
                        self.alloc.pools = truncate_pages(
                            self.alloc.pools, pages, p0 + keep, end,
                            self.page_size)
                    self.slot_pages[s] = release_trailing_pages(
                        self.alloc, pages, -(-(p0 + keep) // self.page_size))
        if restore.any():
            # mid-sequence checkpoint restore: state exactly as after
            # sequentially decoding each row's kept tokens
            cache["ssm"], cache["conv"] = _restore_ckpt_rows(
                cache["ssm"], cache["conv"], ckpts["ssm"], ckpts["conv"],
                jnp.asarray(keeps), jnp.asarray(restore))
        cache["pos"] = jnp.asarray(new_pos, jnp.int32)
        self.cache = cache
        if self.paged:
            self._pos_h = new_pos
        self._tokens = jnp.asarray(nxt[:, None], jnp.int32)
        self._note_kv_bytes()

    def _count_round(self, held, tokens: dict) -> None:
        """Add a decode or verify round's expert-layer counts: `held`
        (slots, C) from the program, of which the first `tokens[slot]`
        positions of each slot were real tokens (the rest is padding)."""
        n = sum(tokens.values())
        self.stats["held_assignments"] += int(
            sum(held[s, :k].sum() for s, k in tokens.items()))
        self.stats["routed_assignments"] += self._assign_per_token * n

    def drain_slot(self, slot: int):
        """Evict + requeue (straggler/failure mitigation). Retries are
        bounded: past `req.max_retries` the request fails visibly into
        `self.failed` instead of requeueing forever."""
        if slot in self.active:
            req = self.active.pop(slot)
            self._live[slot] = False
            self._free_slot_pages(slot)
            if self.spec:
                self.drafter.on_free(slot)
            req.out.clear()
            req.retries += 1
            self.stats["evictions"] += 1
            self.tracer.instant("engine.evict", kind="engine", level=2,
                                rid=req.rid, retries=req.retries)
            if req.retries > req.max_retries:
                req.error = (f"evicted {req.retries} times "
                             f"(max_retries={req.max_retries})")
                self.failed[req.rid] = req
                self.stats["failures"] += 1
            else:
                self.queue.appendleft(req)

    # ------------------------------------------------- non-blocking API ---

    def _free_slot(self) -> Optional[int]:
        """Lowest slot that is neither live nor mid-insert, or None."""
        for s in range(self.slots):
            if not self._live[s] and s not in self._inserting:
                return s
        return None

    @property
    def free_slots(self) -> int:
        return self.slots - len(self.active) - len(self._inserting)

    def estimate_pages(self, prompt_len: int, max_new: int) -> int:
        """Pages the paged insert will demand up front for a prompt of this
        shape (0 for the slab layout / stateless families) — the admission
        headroom check `serving/frontend.py` gates on."""
        if not (self.paged and self.alloc.pools):
            return 0
        total = self._extra + prompt_len
        cap = min(total if self.spec else total + max_new, self.max_len)
        return -(-cap // self.page_size)

    def pool_free_pages(self) -> Optional[int]:
        """Free pages in the KV pool (None off-paged) — interface shared
        with `ReplicaGroup` so the frontend gates either uniformly."""
        if not (self.paged and self.alloc.pools):
            return None
        return self.alloc.free_pages

    def _advance_insert(self, slot: int, req: Request, gen, budget):
        """Drive one insert coroutine until it completes or `budget`
        prefill units are consumed (None = unbounded). Completion removes
        it from `_inserting`; pool exhaustion rolls the slot's page refs
        back and requeues the request at the queue head (the caller decides
        defer vs raise). Returns the remaining budget."""
        try:
            while budget is None or budget > 0:
                next(gen)
                if budget is not None:
                    budget -= 1
        except StopIteration:
            self._inserting.pop(slot, None)
        except PagePoolExhausted:
            self._inserting.pop(slot, None)
            self._free_slot_pages(slot)
            # keep the request visible: it is back at the queue head,
            # never silently dropped (PR 2 hardening contract)
            self.queue.appendleft(req)
            raise
        return budget

    def poll(self, rid: int) -> Optional[Request]:
        """Non-blocking result check: the resolved Request once it has
        finished, failed, or been cancelled; None while still in flight."""
        for d in (self.finished, self.failed, self.cancelled):
            if rid in d:
                return d[rid]
        return None

    def _resolve_cancelled(self, req: Request):
        req.error = "cancelled"
        req.finished_s = time.time()
        self.cancelled[req.rid] = req
        self.stats["cancelled"] += 1

    def cancel(self, rid: int) -> bool:
        """Cancel a request wherever it is in the lifecycle — queued,
        mid-insert, or actively decoding — releasing every resource it
        holds (slot, paged-KV refs, drafter state). The request resolves
        into `self.cancelled` with error='cancelled'. Returns False when
        `rid` is unknown or already resolved (cancel lost the race)."""
        for i, req in enumerate(self.queue):
            if req.rid == rid:
                del self.queue[i]
                self._resolve_cancelled(req)
                return True
        for slot, (req, gen) in list(self._inserting.items()):
            if req.rid == rid:
                gen.close()                      # abandon mid-chunk prefill
                del self._inserting[slot]
                self._free_slot_pages(slot)
                self._resolve_cancelled(req)
                return True
        for slot, req in list(self.active.items()):
            if req.rid == rid:
                del self.active[slot]
                self._live[slot] = False
                self._free_slot_pages(slot)
                if self.spec:
                    self.drafter.on_free(slot)
                req.out.clear()
                self._resolve_cancelled(req)
                return True
        return False

    # --------------------------------------------------------------- run ---

    def step(self, *, max_prefill_chunks: Optional[int] = None,
             defer_admission: bool = False) -> bool:
        """One continuous-batching round: resume in-flight chunked inserts,
        admit queued requests into free slots, then run one batched
        decode/verify phase. Returns whether work remains. `run()` is a
        loop over this; `serving/replicas.py` drives several engines'
        step() interleaved off a shared queue; `serving/frontend.py` pumps
        it with both knobs set.

        max_prefill_chunks: cap on prefill units (chunked-prefill calls /
        slab token-steps) this round. Admission prefill becomes incremental:
        a long prompt spreads over several rounds while already-live slots
        keep decoding — bounding their inter-token latency. None (default)
        drains every insert within the round, byte-identical to the old
        blocking behaviour.
        defer_admission: turn PagePoolExhausted during admission into
        backpressure — the request stays at the queue head, the round keeps
        decoding live slots (which will release pages as they finish), and
        stats['admission_deferred'] counts the stall. The exception still
        raises when nothing is live or inserting, i.e. waiting could never
        free a page (and always with the default defer_admission=False)."""
        t0 = time.perf_counter()
        if self._gap_t0 is not None:    # count the engine's own gap only
            self._gap_t0 = t0
        try:
            with self.tracer.span("engine.step", kind="engine", level=2):
                self._admit(max_prefill_chunks, defer_admission)
                if self.active:
                    name = "engine.verify_round" if self.spec else \
                        "engine.decode_step"
                    with self.tracer.span(name, kind="engine", level=2,
                                          live=len(self.active)):
                        self._spec_step() if self.spec else self._step()
        finally:
            t1 = time.perf_counter()
            self.stats["step_s"] += t1 - t0
            if self._gap_t0 is not None:
                self.stats["host_gap_s"] += t1 - self._gap_t0
                self._gap_t0 = t1
        return bool(self.queue or self.active or self._inserting)

    def _admit(self, budget: Optional[int], defer_admission: bool) -> None:
        """The admission half of `step()`: resume in-flight inserts, then
        start queued requests in free slots, within `budget` prefill
        units."""
        for slot in sorted(self._inserting):
            if budget is not None and budget <= 0:
                break
            req, gen = self._inserting[slot]
            try:
                budget = self._advance_insert(slot, req, gen, budget)
            except PagePoolExhausted:
                if defer_admission and (self.active or self._inserting):
                    self.stats["admission_deferred"] += 1
                    self.tracer.instant("engine.admission_deferred",
                                        kind="engine", level=2, rid=req.rid)
                else:
                    raise
        while self.queue and (budget is None or budget > 0):
            slot = self._free_slot()
            if slot is None:
                break
            req = self.queue.popleft()
            gen = self._insert_co(slot, req)
            self._inserting[slot] = (req, gen)
            try:
                budget = self._advance_insert(slot, req, gen, budget)
            except PagePoolExhausted:
                if defer_admission and (self.active or self._inserting):
                    # backpressure, not failure: decode below frees pages
                    self.stats["admission_deferred"] += 1
                    self.tracer.instant("engine.admission_deferred",
                                        kind="engine", level=2, rid=req.rid)
                    break
                raise

    def run(self, max_steps: int = 10_000, *, strict: bool = True):
        """Drain the queue. If `max_steps` is exhausted with requests still
        queued/active the run is *truncated*: stats["truncations"] is bumped
        and, under `strict` (default), `RunTruncated` is raised — partial
        results must never read as complete."""
        self.stats["runs"] += 1
        with self.tracer.span("engine.run", kind="engine",
                              queued=len(self.queue)):
            while (self.queue or self.active or self._inserting) and \
                    max_steps > 0:
                max_steps -= 1
                self.step()
        if self.queue or self.active or self._inserting:
            self.stats["truncations"] += 1
            if strict:
                raise RunTruncated(
                    f"run() truncated at max_steps with {len(self.active)} "
                    f"active and {len(self.queue)} queued requests",
                    self.finished)
        return self.finished
