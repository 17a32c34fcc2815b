"""Mesh-aware serving parity (DESIGN.md §15).

The bar is *byte-identical rows*: a `ServingEngine` given a `mesh=` (CPU
meshes via the XLA host-device override, so these run in subprocesses like
tests/test_distributed.py) must decode exactly the tokens the single-device
engine decodes — across model families, KV layouts, prefix-cache settings
and speculative decoding, on both a pure-TP (1x2) and a mixed (2x2) mesh.
Sharding is a layout change, never a numerics change.

`ReplicaGroup` (data-parallel engines behind one shared queue) is held to
the same bar in-process, plus the stats contract: per-token counters summed
over replicas equal the single-engine totals on the same workload, and the
aggregate lands in one long-lived dict (`group.stats` stays the same object
across runs — `ServedExtractor` keeps a reference and reads deltas), not a
last-writer-wins merge of replica dicts.
"""
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def run_child(code: str, devices: int = 4, timeout: int = 540,
              prelude: bool = False):
    # dedent BEFORE prepending the (zero-indented) prelude: otherwise the
    # indented snippet would parse as dead code inside the prelude's last def
    prog = (PRELUDE if prelude else "") + textwrap.dedent(code)
    env = {"XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
           "PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin",
           "JAX_PLATFORMS": "cpu", "HOME": "/tmp"}
    res = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert res.returncode == 0, \
        f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr[-3000:]}"
    if "MESH-SKIP" in res.stdout:
        pytest.skip("XLA host-device override ineffective in this environment")
    return res.stdout


# Shared child prelude: skip marker when forcing devices failed, plus the
# engine-run helper every parity child uses. The workload mirrors
# tests/test_paged_kv.py: a 12-token shared prefix + per-request tails.
PRELUDE = """
import jax
if len(jax.devices()) < 4:
    print("MESH-SKIP"); raise SystemExit(0)
from repro.configs import get_smoke_config
from repro.data import lm_data
from repro.models import init_params
from repro.launch.mesh import make_serving_mesh
from repro.serving.engine import Request, ServingEngine

SHARED = [7, 3, 9, 4, 2, 8, 1, 6, 5, 7, 3, 2]
PROMPTS = [SHARED + [10 + i, 20 + i, 30 + i] for i in range(4)]

def build(arch):
    cfg = get_smoke_config(arch).replace(vocab_size=lm_data.VOCAB)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))

def rows(cfg, params, *, layout, pc, spec, mesh=None):
    eng = ServingEngine(cfg, params, slots=2, max_len=64, kv_layout=layout,
                        prefix_cache=pc, prefix_min_len=4, page_size=8,
                        chunk_size=5, spec_decode=spec, mesh=mesh)
    eng.submit_many([Request(rid=i, prompt=p, max_new=4, eos_id=-1,
                             shared_len=len(SHARED))
                     for i, p in enumerate(PROMPTS)])
    done = eng.run()
    return {i: list(done[i].out) for i in range(len(PROMPTS))}
"""


# One representative combo per family, cycling layouts / prefix cache /
# speculation so every feature meets every family class somewhere; the full
# combo matrix runs on the cheapest family below.
FAMILY_COMBOS = [
    ("qwen2.5-3b", "paged", True, "prompt_lookup"),     # dense
    ("deepseek-v2-lite-16b", "paged", False, "off"),    # moe + MLA
    ("falcon-mamba-7b", "slab", True, "off"),           # ssm
    ("zamba2-2.7b", "paged", True, "off"),              # hybrid
    ("whisper-medium", "slab", False, "off"),           # encdec
    ("llava-next-mistral-7b", "paged", False, "prompt_lookup"),  # vlm
]


@pytest.mark.parametrize("arch,layout,pc,spec", FAMILY_COMBOS,
                         ids=[c[0] for c in FAMILY_COMBOS])
def test_mesh_rows_identical_all_families(arch, layout, pc, spec):
    """Single-device vs 1x2 (pure TP) vs 2x2 (DP x TP): byte-identical."""
    out = run_child(f"""
    cfg, params = build({arch!r})
    kw = dict(layout={layout!r}, pc={pc}, spec={spec!r})
    ref = rows(cfg, params, **kw)
    for shape in ((1, 2), (2, 2)):
        got = rows(cfg, params, mesh=make_serving_mesh(shape), **kw)
        assert got == ref, (shape, ref, got)
    print("PARITY-OK", ref)
    """, prelude=True)
    assert "PARITY-OK" in out


def test_mesh_rows_identical_full_matrix():
    """The full {paged,slab} x {pc off,on} x {spec off,prompt_lookup} matrix
    on the dense family, one child process, 2x2 mesh."""
    out = run_child("""
    cfg, params = build("qwen2.5-3b")
    mesh = make_serving_mesh((2, 2))
    n = 0
    for layout in ("paged", "slab"):
        for pc in (False, True):
            for spec in ("off", "prompt_lookup"):
                kw = dict(layout=layout, pc=pc, spec=spec)
                ref = rows(cfg, params, **kw)
                got = rows(cfg, params, mesh=mesh, **kw)
                assert got == ref, (layout, pc, spec, ref, got)
                n += 1
    print("MATRIX-OK", n)
    """, prelude=True, timeout=900)
    assert "MATRIX-OK 8" in out


def test_replica_group_on_mesh_rows_identical():
    """DP replicas stacked on a TP mesh: 2 replicas, each engine on a 1x2
    mesh, rows byte-identical to one single-device engine."""
    out = run_child("""
    from repro.serving.replicas import ReplicaGroup
    cfg, params = build("qwen2.5-3b")
    kw = dict(slots=2, max_len=64, kv_layout="paged", prefix_cache=True,
              prefix_min_len=4, page_size=8, chunk_size=5,
              spec_decode="prompt_lookup")
    reqs = lambda: [Request(rid=i, prompt=p, max_new=4, eos_id=-1,
                            shared_len=len(SHARED))
                    for i, p in enumerate(PROMPTS)]
    eng = ServingEngine(cfg, params, **kw)
    eng.submit_many(reqs())
    ref = {i: list(r.out) for i, r in eng.run().items()}
    grp = ReplicaGroup(cfg, params, replicas=2,
                       mesh=make_serving_mesh((1, 2)), **kw)
    grp.submit_many(reqs())
    got = {i: list(r.out) for i, r in grp.run().items()}
    assert got == ref, (ref, got)
    print("GROUP-MESH-OK")
    """, prelude=True)
    assert "GROUP-MESH-OK" in out


def test_make_serving_mesh_validates():
    from repro.launch.mesh import parse_mesh_shape

    assert parse_mesh_shape("2x2") == (2, 2)
    assert parse_mesh_shape("1,4") == (1, 4)
    assert parse_mesh_shape((4, 1)) == (4, 1)
    for bad in ("3", "2x2x2", "0x4"):
        with pytest.raises(ValueError):
            parse_mesh_shape(bad)
    # device-count validation carries the XLA_FLAGS recipe (subprocess: the
    # parent test process may itself be running with forced devices)
    out = run_child("""
    import jax
    from repro.launch.mesh import make_serving_mesh
    try:
        make_serving_mesh((4, 4))
    except RuntimeError as e:
        assert "xla_force_host_platform_device_count=16" in str(e), e
        print("MESH-VALIDATE-OK")
    """, devices=1)
    assert "MESH-VALIDATE-OK" in out


# ---------------------------------------------------- in-process replicas --
# Single-device: ReplicaGroup parity and the stats-aggregation contract do
# not need a mesh, so these run in the main pytest process.

import jax  # noqa: E402  (after the subprocess-only section on purpose)

from repro.configs import get_smoke_config  # noqa: E402
from repro.data import lm_data  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.serving.engine import Request, ServingEngine  # noqa: E402
from repro.serving.replicas import (PEAK_KEYS, ReplicaGroup,  # noqa: E402
                                    aggregate_stats)

SHARED = [7, 3, 9, 4, 2, 8, 1, 6, 5, 7, 3, 2]

# counters where replica-sum must equal the single-engine total on an
# identical workload (batch-shape-dependent counters like decode_steps or
# max_live legitimately differ across replica splits)
SUM_EQUAL_KEYS = ["prefill_tokens", "prefix_hits", "prefix_saved_tokens",
                  "prefix_inserts", "decode_slot_steps", "draft_tokens",
                  "accepted_tokens", "decode_steps_saved"]


@pytest.fixture(scope="module")
def qwen():
    cfg = get_smoke_config("qwen2.5-3b").replace(vocab_size=lm_data.VOCAB)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _reqs(n=8, max_new=6):
    return [Request(rid=i, prompt=SHARED + [10 + i, 20 + i, 30 + i],
                    max_new=max_new, eos_id=-1, shared_len=len(SHARED))
            for i in range(n)]


ENGINE_KW = dict(slots=2, max_len=64, prefix_cache=True, prefix_min_len=4,
                 page_size=8, chunk_size=5)


@pytest.mark.parametrize("layout,spec", [("paged", "prompt_lookup"),
                                         ("paged", "off"), ("slab", "off")])
def test_replica_group_rows_match_single_engine(qwen, layout, spec):
    cfg, params = qwen
    kw = dict(ENGINE_KW, kv_layout=layout, spec_decode=spec)
    eng = ServingEngine(cfg, params, **kw)
    eng.submit_many(_reqs())
    ref = {i: list(r.out) for i, r in eng.run().items()}
    grp = ReplicaGroup(cfg, params, replicas=2, **kw)
    grp.submit_many(_reqs())
    got = {i: list(r.out) for i, r in grp.run().items()}
    assert got == ref


def test_replica_stats_sum_equals_single_engine(qwen):
    """Regression for last-writer-wins aggregation: every per-token counter
    summed across replicas equals the single-engine total, and the group's
    own dict carries exactly that sum."""
    cfg, params = qwen
    kw = dict(ENGINE_KW, kv_layout="paged", spec_decode="prompt_lookup")
    eng = ServingEngine(cfg, params, **kw)
    eng.submit_many(_reqs())
    eng.run()
    grp = ReplicaGroup(cfg, params, replicas=2, **kw)
    grp.submit_many(_reqs())
    grp.run()
    for k in SUM_EQUAL_KEYS:
        assert grp.stats[k] == eng.stats[k], (
            f"{k}: replica-sum {grp.stats[k]} != single {eng.stats[k]}")
        assert grp.stats[k] == sum(e.stats[k] for e in grp.engines), k
    # at least one counter must be attributable to BOTH replicas, or the
    # "sum" above degenerates into one engine doing all the work
    assert all(e.stats["decode_slot_steps"] > 0 for e in grp.engines)


def test_replica_stats_live_dict_and_run_accounting(qwen):
    """`group.stats` is one long-lived dict updated in place (the extractor
    holds a reference across runs), and runs/truncations are group-level."""
    cfg, params = qwen
    grp = ReplicaGroup(cfg, params, replicas=2, kv_layout="paged", **ENGINE_KW)
    ref = grp.stats
    grp.submit_many(_reqs(4))
    grp.run()
    assert ref is grp.stats and ref["runs"] == 1
    before = ref["prefill_tokens"]
    grp.submit_many(_reqs(4))
    grp.run()
    assert ref is grp.stats and ref["runs"] == 2
    assert ref["prefill_tokens"] > before     # second run visible via old ref
    assert all(e.stats["runs"] == 0 for e in grp.engines)


def test_replica_group_refuses_to_stack_replicas_on_one_device():
    """With several devices visible and no mesh, replicas would all land on
    devices[0]: the group must refuse instead of idling the other devices.
    One replica, or a mesh, is still accepted."""
    out = run_child("""
    import jax
    assert len(jax.devices()) == 2, jax.devices()
    from repro.configs import get_smoke_config
    from repro.data import lm_data
    from repro.launch.mesh import make_serving_mesh
    from repro.models import init_params
    from repro.serving.replicas import ReplicaGroup
    cfg = get_smoke_config("qwen2.5-3b").replace(vocab_size=lm_data.VOCAB)
    params = init_params(cfg, jax.random.PRNGKey(0))
    kw = dict(slots=2, max_len=64, page_size=8)
    try:
        ReplicaGroup(cfg, params, replicas=2, **kw)
    except ValueError as e:
        assert "2 devices are visible" in str(e), e
        print("REFUSED")
    ReplicaGroup(cfg, params, replicas=1, **kw)
    ReplicaGroup(cfg, params, replicas=2,
                 mesh=make_serving_mesh((1, 2)), **kw)
    print("ACCEPTED")
    """, devices=2)
    assert "REFUSED" in out and "ACCEPTED" in out


def test_aggregate_stats_sums_and_peaks():
    a = {"prefill_tokens": 3, "max_live": 2, "kv_bytes_peak": 100}
    b = {"prefill_tokens": 5, "max_live": 4, "kv_bytes_peak": 70, "extra": 1}
    agg = aggregate_stats([a, b])
    assert agg == {"prefill_tokens": 8, "max_live": 4, "kv_bytes_peak": 100,
                   "extra": 1}
    assert set(PEAK_KEYS) == {"max_live", "kv_bytes_peak"}
    into = {"stale": 9}
    out = aggregate_stats([a, b], into=into)
    assert out is into and "stale" not in into and into["max_live"] == 4


def test_replica_group_queue_depth_and_failed(qwen):
    cfg, params = qwen
    grp = ReplicaGroup(cfg, params, replicas=2, queue_depth=3,
                       kv_layout="paged", **ENGINE_KW)
    grp.submit_many(_reqs(3))
    with pytest.raises(RuntimeError, match="queue full"):
        grp.submit(_reqs(4)[3])
    # all-or-nothing: an over-depth batch leaves the queue untouched
    with pytest.raises(RuntimeError, match="queue full"):
        grp.submit_many(_reqs(2))
    assert len(grp.queue) == 3
    grp.run()
    assert set(grp.finished) == {0, 1, 2} and grp.failed == {}


def test_replica_group_shared_prefix_cache_and_pool(qwen):
    """Cross-replica prefix sharing: exactly one insert serves hits on every
    replica, and with the shared paged pool all entry pages live in ONE
    allocator (refcounted across replicas)."""
    cfg, params = qwen
    grp = ReplicaGroup(cfg, params, replicas=2, kv_layout="paged", **ENGINE_KW)
    assert all(e.alloc is grp.engines[0].alloc for e in grp.engines)
    assert all(e.prefix_cache is grp.prefix_cache for e in grp.engines)
    grp.submit_many(_reqs())
    grp.run()
    assert grp.stats["prefix_inserts"] == 1
    assert grp.stats["prefix_hits"] == 7
    # every slot's pages released; only the cached prefix entry pins pages
    alloc = grp.engines[0].alloc
    entry = next(iter(grp.prefix_cache._entries.values()))
    live = len(entry.pages) + (1 if entry.tail_page is not None else 0)
    assert alloc.used_pages == live
