"""The serving engine's phases on the profiler's clock (DESIGN.md §19).

Pins:
  * each jitted engine phase lowers to a program of a stable name, the name
    the device trace's XLA Modules line gives it;
  * the host-sync and first-token counters count what they say: every
    insert reads its first token back once, every round reads a fixed
    number of device values, and their wait and the host gaps between a
    read and the next dispatch lie apart inside the step's time;
  * a `jax.profiler` trace of a served query, taken with no `Tracer`
    attached, holds the program's spans from the session down to the
    engine's chunks and rounds, properly nested;
  * no span stays open across an insert's yield or the session's await.
"""
import asyncio
import glob
import os

import jax
import pytest

from repro.configs import get_smoke_config
from repro.core import Filter, Query, Session
from repro.data import lm_data
from repro.data.corpus import make_swde_corpus
from repro.index.retriever import TwoLevelRetriever
from repro.models import init_params
from repro.obs import LEVEL_FULL, Tracer
from repro.serving import engine as engine_mod
from repro.serving.engine import Request, ServingEngine
from repro.serving.frontend import ServingFrontend

# (engine options, device values each decode or verify round reads back)
LAYOUTS = {
    "paged-spec": (dict(kv_layout="paged", spec_decode="prompt_lookup"), 2),
    "paged": (dict(kv_layout="paged"), 2),
    "slab-spec": (dict(kv_layout="slab", spec_decode="prompt_lookup"), 3),
    "slab": (dict(kv_layout="slab"), 2),
}
PHASES = {"prefill_chunk", "verify_round", "paged_decode", "slab_prefill",
          "slab_decode", "slab_verify"}
SPAN_PREFIXES = ("engine.", "session.", "scheduler.", "extract.",
                 "frontend.", "retrieval.")


@pytest.fixture(scope="module")
def model():
    cfg = get_smoke_config("qwen2.5-3b").replace(vocab_size=lm_data.VOCAB)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _requests(n=6):
    # a shared 20-token prefix: the first request snapshots it, the rest
    # hit it (the slab layout then decodes each suffix token by token)
    return [Request(i, [5] * 20 + list(range(10, 50 + i)), max_new=6,
                    shared_len=20) for i in range(n)]


def _serve(cfg, params, opts, **kw):
    eng = ServingEngine(cfg, params, slots=4, max_len=256, prefix_cache=True,
                        **opts, **kw)
    eng.submit_many(_requests())
    eng.run()
    return eng


def test_phase_programs_carry_stable_names(model, monkeypatch):
    cfg, params = model
    seen = {}
    real = engine_mod._jit

    def recording(fn, **opts):
        jitted = real(fn, **opts)

        def call(*args, **kw):
            seen.setdefault(fn.__name__, (jitted, args, kw))
            return jitted(*args, **kw)
        return call
    monkeypatch.setattr(engine_mod, "_jit", recording)
    for opts, _ in LAYOUTS.values():
        _serve(cfg, params, opts)
    assert set(seen) == PHASES
    for name, (jitted, args, kw) in seen.items():
        text = jitted.lower(*args, **kw).as_text()
        assert f"module @jit_{name} " in text, name


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_host_sync_and_first_token_counters(model, layout):
    cfg, params = model
    opts, reads = LAYOUTS[layout]
    eng = _serve(cfg, params, opts)
    st = eng.stats
    assert st["first_tokens"] == len(eng.finished) == 6
    assert st["decode_steps"] > 0
    assert st["host_syncs"] == st["first_tokens"] + reads * st["decode_steps"]
    assert 0 <= st["sync_wait_s"] <= st["step_s"]
    # the host gaps lie inside the steps, outside the reads' waits
    assert 0 < st["host_gap_s"] <= st["step_s"] - st["sync_wait_s"] + 1e-9
    # each request waited at least for its own prefill
    assert st["ttft_s"] > 0


def test_host_gap_counts_host_work_between_read_and_dispatch(model):
    # drafting runs after the round's (or the insert's) read and before
    # the verify dispatch, while the chip has no phase program queued
    import time
    cfg, params = model
    eng = _serve(cfg, params, LAYOUTS["paged-spec"][0])   # compiles
    before = dict(eng.stats)
    real = eng.drafter.draft_round

    def slow(*args, **kw):
        time.sleep(0.02)
        return real(*args, **kw)
    eng.drafter.draft_round = slow
    eng.submit_many(_requests())
    eng.run()
    st = {k: eng.stats[k] - before[k] for k in before}
    assert st["spec_rounds"] > 0
    assert 0.02 * st["spec_rounds"] <= st["host_gap_s"] <= \
        st["step_s"] - st["sync_wait_s"] + 1e-9


def _swde_session(cfg, params, tracer=None):
    from repro.extract.served import ServedExtractor
    full = make_swde_corpus()
    corpus = full.subset([d for d in sorted(full.docs)
                          if "universities" in d][:16])
    eng = ServingEngine(cfg, params, slots=4, max_len=1024, prefix_cache=True,
                        spec_decode="prompt_lookup", tracer=tracer)
    front = ServingFrontend(eng, max_prefill_chunks=1, tracer=tracer)
    sess = Session(TwoLevelRetriever(corpus),
                   ServedExtractor(corpus, eng, max_new=6, frontend=front),
                   batch_size=4, tracer=tracer)
    query = Query(tables=["universities"],
                  select=[("universities", "university_name")],
                  where=Filter("tuition", "<", 30000, table="universities"))
    return sess, eng, query


def test_profiler_trace_holds_program_spans_nested(model, tmp_path):
    from jax._src.profiler import ProfileData
    cfg, params = model
    sess, eng, query = _swde_session(cfg, params)     # no Tracer attached
    h = sess.submit(query)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        asyncio.run(h.aresult())
    finally:
        jax.profiler.stop_trace()
    assert eng.stats["prefill_chunks"] > eng.stats["first_tokens"] > 0
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    evs = [(ev.start_ns, ev.end_ns, ev.name)
           for plane in ProfileData.from_file(path).planes
           if plane.name.startswith("/host:")
           for line in plane.lines for ev in line.events
           if ev.name.startswith(SPAN_PREFIXES)]
    names = {name for _, _, name in evs}
    assert {"engine.step", "engine.admit", "engine.prefill_chunk",
            "engine.first_token", "engine.verify_round", "engine.draft",
            "engine.accept", "session.step",
            "retrieval.segments"} <= names, str(sorted(names))
    # events of one thread nest: each lies inside the enclosing open one
    parent = {}
    stack = []
    for s, e, name in sorted(evs, key=lambda t: (t[0], -t[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        assert not stack or e <= stack[-1][1], (name, stack[-1][2])
        parent.setdefault(name, set()).add(stack[-1][2] if stack else None)
        stack.append((s, e, name))
    assert parent["engine.prefill_chunk"] <= {"engine.step"}
    assert parent["engine.first_token"] <= {"engine.step"}
    assert parent["engine.verify_round"] == {"engine.step"}
    assert parent["engine.draft"] == parent["engine.accept"] == \
        {"engine.verify_round"}
    assert parent["engine.step"] == {"frontend.pump"}


def test_no_span_open_across_insert_yield_or_session_await(model):
    cfg, params = model
    tr = Tracer(clock="ticks", level=LEVEL_FULL)
    eng = ServingEngine(cfg, params, slots=4, max_len=256, prefix_cache=True,
                        spec_decode="prompt_lookup", tracer=tr)
    eng.submit(Request(0, list(range(10, 110)), max_new=4))  # four chunks
    eng.step(max_prefill_chunks=1)
    assert eng._inserting and tr._stack == []     # the insert has yielded
    eng.run()
    assert tr._stack == [] and eng.stats["first_tokens"] == 1

    tr = Tracer(clock="ticks", level=LEVEL_FULL)
    sess, eng, query = _swde_session(cfg, params, tracer=tr)
    h = sess.submit(query)
    turns = []

    async def watch():
        while not h._done:
            turns.append(list(tr._stack))
            await asyncio.sleep(0)

    async def both():
        await asyncio.gather(h.aresult(), watch())
    asyncio.run(both())
    assert len(turns) > 1 and not any(turns)
    assert tr._stack == []
    spans = {s.sid: s for s in tr.spans}
    for s in tr.spans:
        if s.parent is not None:
            p = spans[s.parent]
            assert p.t0 < s.t0 and s.t1 < p.t1, (s.name, p.name)
    assert {"engine.step", "engine.prefill_chunk", "retrieval.segments",
            "session.step"} <= {s.name for s in tr.spans}
