"""Decode-cache pytree surgery: slot slicing/merging and prefix snapshots.

The serving engine keeps one batched decode cache (leading layer axis,
batch axis 1 — see `init_decode_cache`); requests prefill into a B=1
sub-cache which is then merged into their slot. The shared-prefix KV cache
(`serving/prefix_cache.py`) additionally stores *trimmed* B=1 sub-caches:
length-indexed buffers (`k`/`v`/`ckv`/`krope`, token axis 2) are sliced to
the prefix length so a snapshot costs O(prefix) memory, while pure-state
buffers (SSM `conv`/`ssm`, enc-dec `ck`/`cv`) are kept whole — they are the
exact recurrent/cross state *after* the prefix, which is why snapshots must
be taken by prefilling exactly the prefix (never by slicing a longer
prompt's final state).

Attention masks in decode are gated by `pos` (`layers.attn_decode_apply`
masks `kv_pos < pos+1`), so the zero tail a restored snapshot is padded
with is never attended to.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Buffers indexed by token position on axis 2 ((L, B, max_len, ...)); all
# other cache entries are per-slot state copied whole.
LENGTH_KEYS = ("k", "v", "ckv", "krope")


def slot_cache(cache: dict, slot: int) -> dict:
    """Extract one slot of a batched decode cache as a B=1 sub-cache."""
    sub = {}
    for k, a in cache.items():
        if k == "pos":
            sub[k] = a[slot] if a.ndim else a
        else:
            sub[k] = a[:, slot:slot + 1]
    return sub


def write_slot(cache: dict, sub: dict, slot: int) -> dict:
    """Merge a B=1 sub-cache into `slot` of a batched decode cache."""
    out = dict(cache)
    for k in cache:
        if k == "pos":
            pos = cache["pos"]
            out[k] = (pos.at[slot].set(jnp.asarray(sub["pos"], pos.dtype))
                      if pos.ndim else jnp.asarray(sub["pos"], pos.dtype))
        else:
            out[k] = cache[k].at[:, slot].set(sub[k][:, 0].astype(cache[k].dtype))
    return out


def prefix_snapshot(sub: dict, prefix_len: int) -> dict:
    """Trim a B=1 sub-cache (taken right after prefilling exactly the
    prefix) to O(prefix_len) storage."""
    snap = {}
    for k, a in sub.items():
        if k == "pos":
            snap[k] = jnp.asarray(prefix_len, jnp.int32)
        elif k in LENGTH_KEYS:
            snap[k] = a[:, :, :prefix_len]
        else:
            snap[k] = a
    return snap


def expand_snapshot(snap: dict, max_len: int) -> dict:
    """Zero-pad a trimmed snapshot's token axes back to `max_len` so it is
    shape-compatible with the engine's decode cache."""
    sub = {}
    for k, a in snap.items():
        if k in LENGTH_KEYS and a.shape[2] < max_len:
            pad = [(0, 0)] * a.ndim
            pad[2] = (0, max_len - a.shape[2])
            sub[k] = jnp.pad(a, pad)
        else:
            sub[k] = a
    return sub


def cache_nbytes(tree: dict) -> int:
    """Device bytes held by a cache pytree (for eviction budgets)."""
    return sum(int(a.size) * a.dtype.itemsize
               for a in tree.values() if hasattr(a, "size"))


# ---------------------------------------------------------- paged KV -------
#
# vLLM-style block layout: the length-indexed KV buffers live in a shared
# pool of fixed-size pages instead of per-slot contiguous slabs. A sequence
# is a *page table* (block index -> physical page id); a shared prefix is a
# run of page ids referenced by many tables at once (ref-counted), so a
# prefix-cache hit splices ids instead of copying KV, with copy-on-write on
# the one partially-filled boundary page. Pure-state buffers (SSM conv/ssm,
# enc-dec ck/cv) are not length-indexed and stay in the per-slot state cache.

PAGE_SINK = 0  # reserved page id: scatter target for dead rows, never read


class PagePoolExhausted(RuntimeError):
    """The fixed page pool has no free page left (after prefix eviction)."""


class PageAllocator:
    """Fixed-size KV page pool: free-list allocation + ref-counting.

    Owns the device pools — one array per length-indexed cache key, shaped
    (layer_axis, num_pages, page_size, *tail) — and the host-side page
    metadata. Page 0 is the *sink*: a scratch page dead batch rows scatter
    into; it is never allocated and never read.
    """

    def __init__(self, cfg, num_pages: int, page_size: int):
        from repro.models import init_decode_cache  # local: avoid cycle
        assert num_pages >= 2, "need at least the sink plus one real page"
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        template = init_decode_cache(cfg, 1, self.page_size)
        self.pools = {}
        for key in LENGTH_KEYS:
            if key in template:
                a = template[key]            # (Lax, 1, page_size, *tail)
                shape = (a.shape[0], self.num_pages) + a.shape[2:]
                self.pools[key] = jnp.zeros(shape, a.dtype)
        self.refcount = [0] * self.num_pages
        self._free = list(range(self.num_pages - 1, 0, -1))  # sink excluded

    def shard_pools(self, mesh) -> None:
        """Lay the device pools out over a serving mesh (DESIGN.md §15):
        pages replicated (host-local page ids must dereference identically
        on every device), heads/features over the `model` axis. Call once,
        right after construction — page contents are preserved."""
        from repro.distributed.sharding import pool_specs, to_shardings
        self.pools = jax.device_put(
            self.pools, to_shardings(mesh, pool_specs(self.pools, mesh)))

    # ------------------------------------------------------------ queries --

    @property
    def page_nbytes(self) -> int:
        """Device bytes of one page across every pooled buffer."""
        return sum(int(a[:, 0].size) * a.dtype.itemsize
                   for a in self.pools.values())

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return self.num_pages - 1 - len(self._free)

    @property
    def nbytes_in_use(self) -> int:
        return self.used_pages * self.page_nbytes

    # --------------------------------------------------------- allocation --

    def alloc(self, n: int) -> list:
        """Allocate `n` pages (refcount 1 each). All-or-nothing: raises
        PagePoolExhausted without allocating anything if fewer are free."""
        if n > len(self._free):
            raise PagePoolExhausted(
                f"need {n} pages, {len(self._free)} free "
                f"(pool={self.num_pages}, page_size={self.page_size})")
        ids = [self._free.pop() for _ in range(n)]
        for i in ids:
            self.refcount[i] = 1
        return ids

    def retain(self, ids) -> None:
        """Add a reference to already-live pages (prefix sharing)."""
        for i in ids:
            if self.refcount[i] <= 0:
                raise RuntimeError(f"retain of free page {i}")
            self.refcount[i] += 1

    def release(self, ids) -> None:
        """Drop a reference; a page returns to the free list at zero.
        Releasing an already-free page is a hard error (double free)."""
        for i in ids:
            if self.refcount[i] <= 0:
                raise RuntimeError(f"double free of page {i}")
            self.refcount[i] -= 1
            if self.refcount[i] == 0:
                self._free.append(i)

    def copy_page(self, src: int) -> int:
        """Copy-on-write: allocate a fresh page holding `src`'s contents."""
        (dst,) = self.alloc(1)
        for k in self.pools:
            self.pools[k] = _copy_page_op(self.pools[k], src, dst)
        return dst


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_page_op(pool, src, dst):
    """One-page copy with the pool buffer donated: the update lowers to an
    in-place scatter instead of a whole-pool rewrite per CoW."""
    return pool.at[:, dst].set(pool[:, src])


# Device-side page ops (jit-friendly; page ids arrive as traced int arrays).


@jax.named_scope("kv_gather")
def gather_page_views(pools: dict, table) -> dict:
    """Assemble contiguous per-row KV views through a page table.

    table: (B, nb) int32 of page ids. Returns, per pooled key, a dense
    (layer_axis, B, nb*page_size, *tail) view — the layout `decode_step` /
    `prefill_chunk` already consume, so the paged engine runs the exact
    same model code over gathered views.
    """
    out = {}
    for k, pool in pools.items():
        g = pool[:, table]                       # (Lax, B, nb, ps, *tail)
        out[k] = g.reshape(g.shape[:2] + (g.shape[2] * g.shape[3],) + g.shape[4:])
    return out


@jax.named_scope("kv_scatter")
def scatter_token_pages(pools: dict, dense: dict, write_ids, block_starts,
                        page_size: int) -> dict:
    """Write back each row's active page after a decode step.

    dense: per-key (Lax, B, S, *tail) views returned by the model; the only
    page a decode step dirties for row b is the one holding `pos`, whose
    view offset is block_starts[b]. write_ids[b] is its physical page
    (PAGE_SINK for dead rows). Returns updated pools.
    """
    starts = jnp.asarray(block_starts, jnp.int32)
    out = dict(pools)
    for k, pool in pools.items():
        view = dense[k]

        def one_row(row, s):                     # (Lax, S, *tail) -> page
            return jax.lax.dynamic_slice_in_dim(row, s, page_size, axis=1)
        pages = jax.vmap(one_row, in_axes=(1, 0), out_axes=1)(view, starts)
        out[k] = pool.at[:, jnp.asarray(write_ids, jnp.int32)].set(
            pages.astype(pool.dtype))
    return out


@jax.named_scope("kv_scatter")
def scatter_chunk_pages_rows(pools: dict, view: dict, write_tables, block0s,
                             page_size: int, n_blocks: int) -> dict:
    """Per-row `scatter_chunk_pages` for batched speculative verification.

    view: per-key (Lax, B, nb_ctx*ps, *tail) gathered contexts the verify
    chunk was computed over; row b dirtied blocks [block0s[b], block0s[b] +
    n_blocks) of its own view, whose physical pages are write_tables[b]
    ((B, n_blocks) int32, PAGE_SINK past each row's allocation). Rows never
    share writable pages (the engine CoWs shared boundary pages at insert),
    so duplicate sink ids are the only collisions and the sink is never read.
    """
    b0 = jnp.asarray(block0s, jnp.int32)
    ids = jnp.asarray(write_tables, jnp.int32)               # (B, nb)
    out = dict(pools)
    for k, pool in pools.items():
        v = view[k]
        blocked = v.reshape((v.shape[0], v.shape[1], -1, page_size) + v.shape[3:])

        def one_row(row, s):                     # (Lax, nb_ctx, ps, *tail)
            return jax.lax.dynamic_slice_in_dim(row, s, n_blocks, axis=1)
        pages = jax.vmap(one_row, in_axes=(1, 0), out_axes=1)(blocked, b0)
        flat = pages.reshape((pages.shape[0], -1) + pages.shape[3:])
        out[k] = pool.at[:, ids.reshape(-1)].set(flat.astype(pool.dtype))
    return out


@functools.partial(jax.jit, donate_argnums=(0,))
def _zero_range_op(pool, pid, lo, hi):
    """Zero positions [lo, hi) of one page, pool donated: lowers to an
    in-place scatter (like `_copy_page_op`) instead of a whole-pool copy
    per scrub — pid/lo/hi are traced, so one compile serves every rollback."""
    ps = pool.shape[2]
    mask = (jnp.arange(ps) >= lo) & (jnp.arange(ps) < hi)
    page = pool[:, pid]
    page = jnp.where(mask.reshape((1, ps) + (1,) * (page.ndim - 2)),
                     jnp.zeros((), pool.dtype), page)
    return pool.at[:, pid].set(page)


def truncate_pages(pools: dict, page_ids: list, start: int, end: int,
                   page_size: int) -> dict:
    """Page-truncate (speculative rollback, DESIGN.md §14): zero the KV at
    logical positions [start, end) of a sequence whose block table is
    `page_ids`. Positions past the allocation are skipped (they were
    scattered into the sink). Zeroing — rather than relying only on the
    pos-gated masks — restores the pool bit-exactly to its pre-speculation
    state, so shared/CoW invariants and byte-level page comparisons hold.
    All arguments are host values; returns updated pools.
    """
    out = dict(pools)
    for b in range(start // page_size, -(-end // page_size)):
        if b >= len(page_ids):
            break
        lo = max(start - b * page_size, 0)
        hi = min(end - b * page_size, page_size)
        if lo >= hi:
            continue
        pid = int(page_ids[b])
        for k, pool in out.items():
            out[k] = _zero_range_op(pool, pid, lo, hi)
    return out


def release_trailing_pages(alloc, pages: list, keep_blocks: int) -> list:
    """Ref-release (speculative rollback): drop the references a rejected
    suffix held past the kept block high-water mark. Returns the trimmed
    page table; the released pages return to the allocator's free list at
    refcount zero."""
    keep_blocks = max(0, int(keep_blocks))
    if keep_blocks >= len(pages):
        return pages
    alloc.release(pages[keep_blocks:])
    return pages[:keep_blocks]


@jax.named_scope("kv_scatter")
def scatter_chunk_pages(pools: dict, view: dict, write_ids, block0,
                        page_size: int, n_blocks: int) -> dict:
    """Write back the pages a B=1 prefill chunk dirtied.

    view: per-key (Lax, 1, nb_ctx*ps, *tail) gathered context the chunk was
    computed over (chunk K/V written in place); blocks [block0, block0 +
    n_blocks) cover the chunk (plus CoW slack), write_ids (n_blocks,) their
    physical pages (padded with PAGE_SINK past the allocation).
    """
    b0 = jnp.asarray(block0, jnp.int32)
    out = dict(pools)
    for k, pool in pools.items():
        v = view[k]
        blocked = v.reshape((v.shape[0], -1, page_size) + v.shape[3:])
        pages = jax.lax.dynamic_slice_in_dim(blocked, b0, n_blocks, axis=1)
        out[k] = pool.at[:, jnp.asarray(write_ids, jnp.int32)].set(
            pages.astype(pool.dtype))
    return out
