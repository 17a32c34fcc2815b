"""Paged KV writes in place (models/cache_ops.py `write_pages`).

The three `kv_scatter` writers put each dirtied page into the pool with a
dynamic-update-slice on the page axis, and the engine's phase programs
donate the pool. Two things are pinned down here:

  * the bytes: every page the engine can read holds exactly what the
    page-axis scatter (`pool.at[:, ids].set`) wrote before, with repeated
    PAGE_SINK ids among the targets;
  * the lowering: each compiled phase program aliases every pool leaf from
    input to output, nothing at the top level of the entry or a loop
    computation produces a pool-shaped array except a parameter, a bitcast,
    a tuple, a get-tuple-element, a while or a dynamic-update-slice (on the
    CPU the update comes wrapped in a fusion whose root is one), and a call
    deletes the pool it was handed.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.data import lm_data
from repro.models import init_params
from repro.models.cache_ops import (PAGE_SINK, scatter_chunk_pages,
                                    scatter_chunk_pages_rows,
                                    scatter_token_pages)
from repro.serving.engine import ServingEngine

POOL = (3, 11, 4, 2, 5)      # (Lax, num_pages, page_size, kv_heads, head_dim)


# ------------------------------------------- reference: the page scatter --


def _ref_token(pools, dense, write_ids, block_starts, page_size):
    out = {}
    for k, pool in pools.items():
        def one_row(row, s):
            return jax.lax.dynamic_slice_in_dim(row, s, page_size, axis=1)
        pages = jax.vmap(one_row, in_axes=(1, 0), out_axes=1)(
            dense[k], jnp.asarray(block_starts, jnp.int32))
        out[k] = pool.at[:, jnp.asarray(write_ids, jnp.int32)].set(
            pages.astype(pool.dtype))
    return out


def _ref_rows(pools, view, write_tables, block0s, page_size, n_blocks):
    ids = jnp.asarray(write_tables, jnp.int32)
    out = {}
    for k, pool in pools.items():
        v = view[k]
        blocked = v.reshape((v.shape[0], v.shape[1], -1, page_size) + v.shape[3:])

        def one_row(row, s):
            return jax.lax.dynamic_slice_in_dim(row, s, n_blocks, axis=1)
        pages = jax.vmap(one_row, in_axes=(1, 0), out_axes=1)(
            blocked, jnp.asarray(block0s, jnp.int32))
        flat = pages.reshape((pages.shape[0], -1) + pages.shape[3:])
        out[k] = pool.at[:, ids.reshape(-1)].set(flat.astype(pool.dtype))
    return out


def _ref_chunk(pools, view, write_ids, block0, page_size, n_blocks):
    out = {}
    for k, pool in pools.items():
        v = view[k]
        blocked = v.reshape((v.shape[0], -1, page_size) + v.shape[3:])
        pages = jax.lax.dynamic_slice_in_dim(
            blocked, jnp.asarray(block0, jnp.int32), n_blocks, axis=1)
        out[k] = pool.at[:, jnp.asarray(write_ids, jnp.int32)].set(
            pages.astype(pool.dtype))
    return out


def _ids(rng, n):
    """n write targets: distinct real pages, with PAGE_SINK repeated."""
    real = rng.permutation(np.arange(1, POOL[1]))[:n]
    ids = np.where(rng.random(n) < 0.4, PAGE_SINK, real)
    ids[:2] = PAGE_SINK                          # at least one repeat
    return ids.astype(np.int32)


def _case(kind, rng):
    """(writer, reference, args after `pools`) for one writer."""
    lax_, _, ps, h, d = POOL
    if kind == "token":                          # decode: one page a row
        B, S = 4, 6 * ps
        dense = {k: jnp.asarray(rng.normal(size=(lax_, B, S, h, d)),
                                jnp.bfloat16) for k in "kv"}
        starts = rng.integers(0, S // ps, B) * ps
        return (scatter_token_pages, _ref_token,
                (dense, _ids(rng, B), starts, ps))
    if kind == "rows":                           # verify: B x nb pages
        B, nb_ctx, nb = 3, 6, 2
        view = {k: jnp.asarray(rng.normal(size=(lax_, B, nb_ctx * ps, h, d)),
                               jnp.bfloat16) for k in "kv"}
        tables = _ids(rng, B * nb).reshape(B, nb)
        b0s = rng.integers(0, nb_ctx + 1, B)     # past the end: clamped
        return (scatter_chunk_pages_rows, _ref_rows,
                (view, tables, b0s, ps, nb))
    nb_ctx, nb = 8, 3                            # prefill: nb pages, B=1
    view = {k: jnp.asarray(rng.normal(size=(lax_, 1, nb_ctx * ps, h, d)),
                           jnp.bfloat16) for k in "kv"}
    return (scatter_chunk_pages, _ref_chunk,
            (view, _ids(rng, nb), int(rng.integers(0, nb_ctx + 1)), ps, nb))


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("kind", ["token", "rows", "chunk"])
def test_page_writers_match_page_scatter(kind, seed):
    rng = np.random.default_rng(seed)
    writer, ref, args = _case(kind, rng)
    pools = {k: jnp.asarray(rng.normal(size=POOL), jnp.float32) for k in "kv"}
    want = ref(pools, *args)
    # eager, and jitted over a donated copy as the engine runs it
    static = tuple(range(4, len(args) + 1))      # page_size (, n_blocks)
    jitted = jax.jit(writer, static_argnums=static, donate_argnums=(0,))
    for got in (writer(pools, *args),
                jitted({k: jnp.array(a) for k, a in pools.items()}, *args)):
        for k in pools:
            g, w = np.asarray(got[k]), np.asarray(want[k])
            np.testing.assert_array_equal(g[:, 1:], w[:, 1:])  # sink unread


# --------------------------------------- the lowering of the phase programs --


def _computations(hlo: str) -> dict:
    """name -> instruction lines, for each computation of an HLO module."""
    comps, name = {}, None
    for line in hlo.splitlines():
        m = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if m:
            name = "ENTRY" if m.group(1) else m.group(2)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


_INSTR = re.compile(r"\s*(?:ROOT )?%?\S+ = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(")
_ALLOWED = {"parameter", "bitcast", "tuple", "get-tuple-element", "while",
            "dynamic-update-slice"}


def _pool_writers(hlo: str, pool_dims: str) -> list:
    """Top-level ops of the entry and of every loop body or condition it
    reaches that produce an array of the pool's shape, other than
    `_ALLOWED` and in-place update fusions (root: a dynamic-update-slice)."""
    comps = _computations(hlo)

    def root_op(comp):
        for line in comps[comp]:
            if line.lstrip().startswith("ROOT"):
                return _INSTR.match(line).group(3)

    bad, todo, seen = [], ["ENTRY"], set()
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for line in comps[comp]:
            if " while(" in line:
                todo += re.findall(r"(?:condition|body)=%?([\w.\-]+)", line)
            m = _INSTR.match(line)
            if not m or m.group(2) != pool_dims or m.group(3) in _ALLOWED:
                continue
            called = re.search(r"calls=%?([\w.\-]+)", line)
            if m.group(3) == "fusion" and called and \
                    root_op(called.group(1)) == "dynamic-update-slice":
                continue
            bad.append(line.strip()[:160])
    return bad


def _aliased_params(hlo: str) -> set:
    header = hlo.splitlines()[0]
    alias = re.search(r"input_output_alias=\{(.*?) \}", header)
    return {int(p) for p in re.findall(r"\}: \((\d+),",
                                       alias.group(1) if alias else "")}


def _entry_params(hlo: str) -> list:
    """Entry parameter types, in parameter order."""
    sig = next(line for line in hlo.splitlines() if line.startswith("ENTRY"))
    sig = sig[sig.index("(") + 1:sig.index(") ->")]
    return re.findall(r"[\w.\-]+: (\w+\[[\d,]*\])", sig)


@pytest.fixture(scope="module")
def engine():
    cfg = get_smoke_config("qwen2.5-3b").replace(vocab_size=lm_data.VOCAB)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return ServingEngine(cfg, params, slots=2, max_len=64, page_size=8,
                         chunk_size=5, prefix_cache=True,
                         spec_decode="prompt_lookup")


def _phase(eng, name):
    """(jitted program, its arguments, position of the pools among them)."""
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    pools, n_ctx, B = eng.alloc.pools, 4, eng.slots
    if name == "prefill_chunk":
        cs, ps = eng.chunk_size, eng.page_size
        nb = (cs + ps - 2) // ps + 1
        state = {k: (i32() if k == "pos" else
                     jnp.zeros(a.shape[:1] + (1,) + a.shape[2:], a.dtype))
                 for k, a in eng.cache.items()}
        return (eng._chunk_fn(n_ctx, nb, False),
                (eng.params, state, pools, i32(n_ctx), i32(1, cs), i32(),
                 i32(nb), i32()), 2)
    if name == "verify_round":
        fn, nb = eng._verify_fn(n_ctx)
        return (fn, (eng.params, eng.cache, pools, i32(B, n_ctx),
                     i32(B, eng.spec_k + 1), i32(B, nb), i32(B)), 2)
    return (eng._paged_decode, (eng.params, i32(B, 1), eng.cache, pools,
                                i32(B, n_ctx), i32(B)), 3)


@pytest.mark.parametrize("name", ["prefill_chunk", "verify_round",
                                  "paged_decode"])
def test_phase_program_writes_pool_in_place(engine, name):
    fn, args, at = _phase(engine, name)
    pools = args[at]
    hlo = fn.lower(*args).compile().as_text()
    shape = next(iter(pools.values())).shape
    dims = ",".join(map(str, shape))
    pool_params = {i for i, t in enumerate(_entry_params(hlo))
                   if t.endswith(f"[{dims}]")}
    assert len(pool_params) == len(pools), (pool_params, list(pools))
    assert pool_params <= _aliased_params(hlo), hlo.splitlines()[0][:400]
    assert _pool_writers(hlo, dims) == []
    out = fn(*args)
    assert all(a.is_deleted() for a in pools.values())
    engine.alloc.pools = out[2]              # (logits, state, pools, ...)
    assert not any(a.is_deleted() for a in engine.alloc.pools.values())
