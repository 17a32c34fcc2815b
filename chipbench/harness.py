"""One run of one benchmark cell: set-up, the measured window of query
waves, the per-layer readings of a traced run, and the comparison that
decides `correct`.

A wave is what K analysts do who each submit one query and wait for its
rows: a fresh `Session` over a fresh `retriever.fork()`, the mix's K queries
submitted together and drained, over one `ServingEngine` and
`ServedExtractor` that every wave shares (so the engine's prefix cache
carries over, as in a running deployment). Every wave replays the same K
queries, so every wave does the same work. Set-up builds the corpus, the
retriever, the weights (on the device, from the seed) and the oracle's rows,
then runs one wave that compiles every program the window uses and fills the
prefix cache. The window runs waves back to back for `--seconds`; the wave
in flight at the deadline is stopped at the engine's next step and credited
with the share of a whole wave's engine tokens it had processed.

`correct` compares what the window produced: the rows of every query of its
whole waves against the same queries run through `OracleExtractor`, every
ranking its retrieval returned against a float64 numpy ranking, and, for a
sample of the requests of the window's first wave, the served tokens and the
logits the engine computed for them (kept as the engine's programs return
them, at the engine's next step) against the configuration's plain float32
reference, computed once the window has closed and the engine is freed.
"""
from __future__ import annotations

import asyncio
import gc
import importlib.util
import json
import random
import statistics
import sys
import tempfile
import time
from functools import partial
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE_DIR = ROOT / ".jax_cache"

# Limits of the exact comparisons `correct` makes. Each configuration file
# gives its own limits for the model's numbers under "limits": `logit_gap`,
# the widest gap of a served token's reference logit below the reference's
# best, and `logit_diff`, the widest |engine logit - reference logit|.
# PERF.md gives the readings each was set from.
LIMITS = {
    "rows_mismatched_queries": 0,  # queries whose rows differ from the oracle's
    "ranking_errors": 0,           # rankings float64 numpy does not allow
    "unread_tokens": 0,            # sampled served tokens with no logits kept
}
RANK_TOL = 1e-5         # distances closer than this may trade places
N_SAMPLE = 16           # requests whose served tokens the reference checks


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"missing {path}")
    return json.loads(path.read_text())


def load_benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def load_config(name: str) -> dict:
    return load_json(HERE / "configs" / f"{name}.json")


def load_module(rel: str):
    """The module `chipbench/<rel>.py`, found by name."""
    path = HERE / f"{rel}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no module {rel!r} at {path}")
    name = "chipbench_" + rel.replace("/", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric(name: str):
    """The reader of one per-layer metric: `metrics/<name>.py`, whose
    `read(r)` returns a number or None where it finds nothing to read."""
    return load_module(f"metrics/{name}")


def load_reference(conf: dict):
    """The configuration's plain reference and weight maker, the module its
    file names under "reference" (`make_weights`, `compare_requests`)."""
    return load_module(conf["reference"])


def load_peaks(kind: str) -> dict:
    peaks = load_json(HERE / "peaks.json")["devices"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return peaks[kind]


# ----------------------------------------------------------------- jax ---


def enable_cache() -> None:
    """Persistent compilation cache at a fixed path in the checkout, every
    program kept, so a warm run compiles nothing."""
    import jax
    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)


class CompileCounter:
    """Counts XLA compilations (persistent-cache loads included) through
    jax.monitoring."""

    def __init__(self):
        import jax.monitoring as mon
        self.n = self.hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def model_config(conf: dict):
    """The program's ModelConfig for a configuration file: its "program"
    entry names the program's model family and, for each ModelConfig field,
    the configuration key that sets it."""
    from repro.models.config import ModelConfig
    prog = conf["program"]
    return ModelConfig(name=conf["name"], family=prog["family"],
                       **{field: conf[key]
                          for field, key in prog["fields"].items()})


# ------------------------------------------------------------- proxies ---


class WindowClosed(Exception):
    """Raised at the engine's first step after the window's deadline."""


def mesh_page_pool(cfg, serving: dict, mesh):
    """The engine's paged KV pool, of the engine's default size, laid out
    over `mesh` by the program's own `PageAllocator.shard_pools`.

    `ServingEngine(mesh=)` makes its pool whole on the first chip before
    laying it out over the mesh, beside its decode cache, also made whole
    there: at 32 of Qwen3-32B's layers the two (4.8 and 4.3 GB) do not fit
    beside that chip's 8.6 GB of weights. Made here before the weights are
    drawn, the whole pool has left the first chip when they arrive, and the
    engine takes the laid-out one (`page_allocator=`)."""
    from repro.models.cache_ops import PageAllocator
    pages = (serving["slots"] + 4) * serving["max_len"] \
        // serving["page_size"] + 1
    alloc = PageAllocator(cfg, pages, serving["page_size"])
    alloc.shard_pools(mesh)
    return alloc


def windowed_engine(cfg, params, serving: dict, mesh=None, pool=None):
    """The program's ServingEngine, set as the deployment runs it (on
    `mesh`, a serving mesh, with `pool` from `mesh_page_pool`, where the
    configuration asks for one), with
    benchmark-side hooks: each step first checks the window's deadline;
    every request submitted is kept for the wave that submitted it; and for
    the first request of each prompt in `tap` (or the first one submitted,
    with `tap_first`), the logits from which each served token was taken are
    kept as the engine's own programs returned them: the last prefill
    chunk's for the first token, each verify round's for the tokens that
    round emitted (the cells serve with speculation; a token served by a
    plain decode step is left unread, which fails `unread_tokens`). Kept
    rows are copied on the device into `tap_buf`, a block of `max_new` rows
    per tapped request (replicated over the mesh), and read back once the
    window has closed: nothing is copied to the host inside the window."""
    import jax
    import jax.numpy as jnp
    from repro.serving.engine import ServingEngine

    max_new = serving["max_new"]
    scratch = N_SAMPLE * max_new          # the row that takes unused picks
    replicated, out = None, {}
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        replicated = NamedSharding(mesh, PartitionSpec())
        out = {"out_shardings": replicated}

    @partial(jax.jit, donate_argnums=0, **out)
    def put(buf, logits, slot, n, base):
        """Rows slot*K .. slot*K+n-1 of `logits` (rows, K, V) into rows
        base .. base+n-1 of `buf`."""
        K, V = logits.shape[-2], logits.shape[-1]
        j = jnp.arange(K)
        dst = jnp.where(j < n, base + j, scratch)
        return buf.at[dst].set(logits.reshape(-1, V)[slot * K + j])

    class Engine(ServingEngine):
        deadline = None

        def step(self, **kw):
            if self.deadline is not None and \
                    time.perf_counter() >= self.deadline:
                raise WindowClosed
            with jax.profiler.TraceAnnotation("chipbench.engine.step"):
                return super().step(**kw)

        def submit_many(self, reqs):
            reqs = list(reqs)
            self.wave_requests.extend(reqs)
            for r in reqs:
                key = tuple(r.prompt) if self.tap else None
                if (self.tap_first or key in self.tap) and \
                        len(self.tap_base) < N_SAMPLE:
                    self.tap_base[r.rid] = len(self.tap_base) * max_new
                    self.tap = self.tap - {key}
                    self.tap_first = False
            return super().submit_many(reqs)

        def _keep(self, rid, start, logits, row, n):
            n = min(n, max_new - start)
            self.tap_buf = put(self.tap_buf, logits, np.int32(row),
                               np.int32(n), np.int32(self.tap_base[rid] + start))
            self.tap_read.setdefault(rid, set()).update(
                range(start, start + n))

        def tapped_logits(self, rid, n):
            """The kept logits of the first `n` tokens served for `rid`, or
            None where some of them were not kept."""
            if not set(range(n)) <= self.tap_read.get(rid, set()):
                return None
            if self._tap_host is None:
                self._tap_host = np.asarray(self.tap_buf)
            base = self.tap_base[rid]
            return self._tap_host[base:base + n]

        def _insert_paged_co(self, slot, req):
            logits = yield from super()._insert_paged_co(slot, req)
            if req.rid in self.tap_base:
                self._keep(req.rid, 0, logits, 0, 1)   # (1, 1, V)
            return logits

        def _verify_fn(self, n_ctx):
            fn, nb = super()._verify_fn(n_ctx)

            def verify(*args):
                out = fn(*args)
                self._round_logits = out[0]      # (slots, C, V)
                return out
            return verify, nb

        def _spec_step(self):
            before = {s: (r, len(r.out)) for s, r in self.active.items()
                      if r.rid in self.tap_base}
            self._round_logits = None
            super()._spec_step()
            for s, (r, n0) in before.items():
                if len(r.out) > n0:
                    self._keep(r.rid, n0, self._round_logits, s,
                               len(r.out) - n0)
            self._round_logits = None

    on_mesh = {} if mesh is None else {"mesh": mesh, "page_allocator": pool}
    engine = Engine(cfg, params, slots=serving["slots"],
                    max_len=serving["max_len"], kv_layout="paged",
                    page_size=serving["page_size"],
                    chunk_size=serving["chunk_size"],
                    prefix_cache=serving["prefix_cache"],
                    spec_decode=serving["spec_decode"], **on_mesh)
    engine.wave_requests = []
    engine.tap, engine.tap_first = frozenset(), False
    engine.tap_buf = jnp.zeros((scratch + 1, cfg.vocab_size), jnp.float32,
                               device=replicated)
    engine.tap_base, engine.tap_read, engine._tap_host = {}, {}, None
    return engine


class TimedRetriever:
    """The forked retriever behind the session, with the host time of its
    public calls summed (they return numpy or Python values, so the work has
    finished when they return) and marked in the profiler's trace."""

    TIMED = frozenset({"candidate_docs", "refine_candidates", "segments",
                       "segment_tokens", "prefetch_segments", "add_evidence",
                       "finalize_thresholds", "score_margin"})

    def __init__(self, inner):
        self._inner = inner
        self.seconds = 0.0

    def __getattr__(self, name):
        attr = getattr(self._inner, name)
        if name not in self.TIMED:
            return attr
        import jax

        def timed(*args, **kw):
            t = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation(f"chipbench.retrieval.{name}"):
                    return attr(*args, **kw)
            finally:
                self.seconds += time.perf_counter() - t
        return timed


class RecordingIndex:
    """A vector index that keeps what each search was asked and answered,
    for the float64 check once the window has closed."""

    def __init__(self, inner, book: list):
        self._inner, self._book = inner, book

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def __len__(self):
        return len(self._inner)

    def range_search(self, q, tau):
        out = self._inner.range_search(q, tau)
        self._book.append(("range", self._inner, np.atleast_2d(q), [tau], [out]))
        return out

    def range_search_many(self, qs, taus):
        out = self._inner.range_search_many(qs, taus)
        self._book.append(("range", self._inner, np.atleast_2d(qs),
                           list(taus), out))
        return out

    def search(self, q, k):
        out = self._inner.search(q, k)
        qs = np.atleast_2d(q)
        self._book.append(("knn", self._inner, qs, [k] * len(qs), out))
        return out


def recorded_fork(retriever, book: list):
    fork = retriever.fork()
    fork.seg_index = {d: RecordingIndex(ix, book)
                      for d, ix in fork.seg_index.items()}
    fork.doc_index = RecordingIndex(fork.doc_index, book)
    return fork


# ---------------------------------------------------------------- wave ---


def _stats(engine) -> dict:
    from repro.obs.metrics import ENGINE_STATS
    return {k: engine.stats[k] for k in ENGINE_STATS}


def _processed(delta: dict) -> int:
    """Engine tokens processed: prompt tokens prefilled plus tokens served."""
    return (delta["prefill_tokens"] + delta["decode_slot_steps"]
            + delta["decode_steps_saved"])


def run_wave(ctx, *, book=None) -> dict:
    """One wave; returns its record. `whole` is False where the window's
    deadline stopped it."""
    import jax
    from repro.core import Session

    engine = ctx["engine"]
    retr = TimedRetriever(recorded_fork(ctx["retriever"], book)
                          if book is not None else ctx["retriever"].fork())
    serving = ctx["serving"]
    session = Session(retr, ctx["extractor"],
                      sample_rate=serving["sample_rate"],
                      batch_size=serving["slots"])
    engine.wave_requests = []
    s0 = _stats(engine)
    t0 = time.perf_counter()
    handles = [session.prepare(q).submit() for q in ctx["queries"]]
    done = {}

    async def one(i, h):
        await h.aresult()
        done[i] = time.perf_counter() - t0

    async def drive():
        await asyncio.gather(*(one(i, h) for i, h in enumerate(handles)))

    whole = True
    with jax.profiler.TraceAnnotation("chipbench.wave"):
        try:
            asyncio.run(drive())
        except WindowClosed:
            whole = False
    t1 = time.perf_counter()
    s1 = _stats(engine)
    delta = {k: s1[k] - s0[k] for k in s0}
    return {
        "whole": whole, "t0": t0, "t1": t1, "seconds": t1 - t0,
        "query_s": [done[i] for i in sorted(done)],
        "rows": [h.result().rows for h in handles] if whole else None,
        "tokens": _processed(delta), "engine": delta,
        "scheduler": session.scheduler.stats.snapshot(),
        "ledger": session.ledger.snapshot(),
        "retrieval_s": retr.seconds,
        "requests": [r for r in engine.wave_requests if r.done],
    }


# ---------------------------------------------------------- correctness ---


def _canon(rows: list) -> list:
    return sorted(json.dumps(r, sort_keys=True, default=str) for r in rows)


def oracle_rows(corpus, retriever, queries, serving) -> list:
    """Rows of the wave's queries with the exact `OracleExtractor` in place
    of the served model, on the same session path."""
    from repro.core import Session
    from repro.extract import OracleExtractor
    session = Session(retriever.fork(), OracleExtractor(corpus, noisy=False),
                      sample_rate=serving["sample_rate"],
                      batch_size=serving["slots"])
    handles = [session.prepare(q).submit() for q in queries]
    session.drain()
    return [_canon(h.result().rows) for h in handles]


def ranking_errors(book: list) -> tuple:
    """Rankings in `book` that no float64 ranking of the same rows allows:
    ids missing or extra beyond RANK_TOL of the cut, or out of order beyond
    RANK_TOL. Returns (errors, rankings checked)."""
    errors = checked = 0
    for kind, ix, qs, params, outs in book:
        if type(ix).__name__ != "ExactIndex" or ix.n_tombstones:
            continue
        emb = np.asarray(ix.emb, np.float64)
        row = {id_: i for i, id_ in enumerate(ix.ids)}
        for q, par, (got_ids, _) in zip(qs, params, outs):
            d = np.sqrt(((emb - np.asarray(q, np.float64)) ** 2).sum(-1))
            order = np.argsort(d, kind="stable")
            if kind == "range":
                want = [i for i in order if d[i] < par]
                cut = par
            else:
                want = list(order[:par])
                cut = d[order[min(par, len(d)) - 1]] if len(d) else 0.0
            got = [row[g] for g in got_ids]
            odd = set(got) ^ set(want)
            bad = any(abs(d[i] - cut) >= RANK_TOL for i in odd)
            gd = d[got]
            bad |= bool(np.any(np.diff(gd) < -RANK_TOL))
            ties = np.diff(gd) == 0
            bad |= bool(np.any(np.diff(got)[ties] < 0))
            errors += bad
            checked += 1
    return errors, checked


def sample_prompts(requests: list, seed: int) -> frozenset:
    """Prompts of N_SAMPLE of the finished `requests`, drawn from the seed,
    the longest among them."""
    reqs = sorted(requests, key=lambda r: r.rid)
    if not reqs:
        return frozenset()
    longest = max(reqs, key=lambda r: (len(r.prompt) + len(r.out), -r.rid))
    rest = [r for r in reqs if r is not longest]
    pick = random.Random(seed).sample(rest, min(N_SAMPLE - 1, len(rest)))
    return frozenset(tuple(r.prompt) for r in [longest] + pick)


def tapped_requests(engine, wave: dict) -> tuple:
    """(prompt, served tokens, the engine's logits of each) of the requests
    of `wave` whose logits the engine kept; and the number of their served
    tokens with no logits kept."""
    seqs, unread = [], 0
    for r in wave["requests"]:
        if r.rid not in engine.tap_base:
            continue
        logits = engine.tapped_logits(r.rid, len(r.out))
        if logits is None:
            unread += len(r.out) - len(engine.tap_read.get(r.rid, ()))
        elif r.out:
            seqs.append((list(r.prompt), list(r.out), logits))
    return seqs, unread


# ------------------------------------------------------------ set-up ---


def build(conf: dict, mix: dict, seed: int, mesh=None) -> dict:
    """Set-up of a run; with `mesh`, the weights are drawn straight into the
    program's serving layout over it and the engine runs on it."""
    import jax
    from repro.extract.served import ServedExtractor
    from repro.index.retriever import TwoLevelRetriever

    from chipbench import traffic

    serving = conf["serving"]
    t = time.perf_counter()
    corpus = traffic.build_corpus(mix)
    retriever = TwoLevelRetriever(corpus)
    queries = traffic.wave_queries(mix, corpus)
    order = traffic.submit_order(len(queries), seed)
    queries = [queries[i] for i in order]
    log(f"set-up: corpus, retriever and queries {time.perf_counter() - t:.2f} s")
    t = time.perf_counter()
    reference = load_reference(conf)
    cfg = model_config(conf)
    shardings = pool = None
    if mesh is not None:
        from repro.distributed.sharding import param_shardings
        pool = mesh_page_pool(cfg, serving, mesh)
        shardings = param_shardings(
            cfg, jax.eval_shape(lambda: reference.make_weights(conf, seed)),
            mesh, serving=True)
    params = reference.make_weights(conf, seed, shardings)
    jax.block_until_ready(params)
    log(f"set-up: weights {time.perf_counter() - t:.2f} s")
    engine = windowed_engine(cfg, params, serving, mesh, pool)
    extractor = ServedExtractor(corpus, engine, max_new=serving["max_new"])
    return {"conf": conf, "mix": mix, "serving": serving, "corpus": corpus,
            "retriever": retriever, "queries": queries, "params": params,
            "reference": reference, "engine": engine,
            "extractor": extractor}


def check_devices(chips: int, require_tpu: bool):
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU, JAX found "
                         f"{devices[0].platform}")
    if len(devices) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX "
                         f"found {len(devices)}")
    return devices[:chips]


def serving_mesh(serving: dict, devices: list):
    """The (data, model) serving mesh that a configuration asks for under
    `serving["mesh"]`, over the cell's devices; None where it asks for
    none. Its size has to be the cell's number of chips."""
    shape = serving.get("mesh")
    if shape is None:
        return None
    if int(np.prod(shape)) != len(devices):
        raise SystemExit(f"chipbench: the serving mesh {shape} needs "
                         f"{int(np.prod(shape))} chips, the cell asks for "
                         f"{len(devices)}")
    from repro.launch.mesh import _make_mesh
    return _make_mesh(tuple(shape), ("data", "model"), devices=devices)


# ----------------------------------------------------------------- run ---


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True, conf=None,
             peaks=None, controls=(), one_wave: bool = False) -> dict:
    """One run of a cell; returns the result object that `run.py` prints.
    Tests run it off the chip with `require_tpu=False`, a small `conf` and
    made-up `peaks`. `controls` names lower precisions ("int8", "fp8") in
    which the reference is put in the program's place and judged by the
    same limits, under the result's "controls"; with `one_wave` the window
    is one whole wave. `control.py` runs both; benchmark runs neither."""
    from chipbench import devtrace, traffic

    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"chipbench: no workload {workload!r} in "
                         f"BENCHMARK.json")
    cell = cells[workload]
    devices = check_devices(cell["chips"], require_tpu)
    import jax
    enable_cache()
    counter = CompileCounter()
    dev = devices[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)} "
        f"(jax {jax.__version__})")
    conf = conf or load_config(cell["config"])
    mix = traffic.load_mix(cell["traffic"])

    ctx = build(conf, mix, seed, serving_mesh(conf["serving"], devices))
    t = time.perf_counter()
    want = oracle_rows(ctx["corpus"], ctx["retriever"], ctx["queries"],
                       ctx["serving"])
    log(f"set-up: oracle rows {time.perf_counter() - t:.2f} s")
    engine = ctx["engine"]
    engine.tap_first = True        # compiles the logit tap's slicing
    warm = run_wave(ctx)
    log(f"set-up: warm-up wave {warm['seconds']:.1f} s, "
        f"{counter.n} compilations ({counter.hits} from the persistent "
        f"cache)")
    # every wave submits the same prompts: tap the seed's sample of them in
    # the window's first wave
    engine.tap = sample_prompts(warm["requests"], seed)
    engine.tap_base, engine.tap_read = {}, {}

    book: list = []
    waves: list = []
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    n0 = counter.n
    t_win = time.perf_counter()
    while True:
        first = not waves
        # the traced wave (a traced run's first) always runs to its end
        tracing = trace and first
        engine.deadline = None if tracing or one_wave else t_win + seconds
        if tracing:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            wave = run_wave(ctx, book=book)
        finally:
            if tracing:
                jax.profiler.stop_trace()
        if first:
            engine.tap = frozenset()
        waves.append(wave)
        if one_wave or not wave["whole"] or \
                time.perf_counter() >= t_win + seconds:
            break
    t_end = time.perf_counter()
    engine.deadline = None
    compiles = counter.n - n0
    log(f"window: {len(waves)} waves, {compiles} compilations inside the "
        f"window")

    whole = [w for w in waves if w["whole"]]
    if not whole:
        raise SystemExit("chipbench: the window holds no whole wave")
    K = len(ctx["queries"])
    share = 0.0 if waves[-1]["whole"] else \
        waves[-1]["tokens"] / whole[0]["tokens"]
    window_s = t_end - t_win
    metrics = {
        "query_s": statistics.median(t for w in whole for t in w["query_s"]),
        "queries_per_min": K * (len(whole) + share) / (window_s / 60.0),
        "setup_s": t_win - t_start,
    }
    log(f"window: {len(whole)} whole waves + {share:.4f} of one in "
        f"{window_s:.2f} s")

    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devices)
    readings = None
    if trace:
        import shutil
        pd = devtrace.load(devtrace.xplane_file(trace_dir))
        reduced = devtrace.reduce(pd, len(devices))
        t = time.perf_counter()
        collectives = devtrace.collectives(pd, len(devices))
        log(f"trace: collective ops read in {time.perf_counter() - t:.2f} s; "
            f"program -> [device s, collective s] per chip: {collectives}")
        del pd
        shutil.rmtree(trace_dir, ignore_errors=True)
        readings = Readings(conf, waves[0], K, reduced,
                            peaks or load_peaks(dev.device_kind),
                            len(devices), collectives)

    # ---- the comparison, once the window has closed and the engine is freed
    rows_bad = sum(_canon(r) != want[i] for w in whole
                   for i, r in enumerate(w["rows"]))
    rank_bad, rank_n = ranking_errors(book)
    seqs, unread = tapped_requests(engine, waves[0])
    failed = len(engine.failed)
    attempted = K * len(waves)
    params, reference = ctx["params"], ctx["reference"]
    del ctx, engine, waves, whole, book, warm
    gc.collect()
    t = time.perf_counter()
    ref = reference.compare_requests(
        conf, params, seqs, seq_len=conf["serving"]["max_len"],
        quants=tuple(controls)) if seqs else {"tokens": 0}
    log(f"check: reference {time.perf_counter() - t:.2f} s, {rank_n} "
        f"rankings against float64 numpy, {ref['tokens']} served tokens of "
        f"{len(seqs)} requests and their logits against the float32 "
        f"reference")
    base = {"rows_mismatched_queries": rows_bad, "ranking_errors": rank_bad,
            "unread_tokens": unread}
    limits = {**LIMITS, **conf["limits"]}
    sound = failed == 0 and ref["tokens"] > 0

    def judge(model: str) -> tuple:
        r = ref.get(model, {})
        values = dict(base, logit_gap=r.get("gap", float("inf")),
                      logit_diff=r.get("diff", float("inf")))
        checks = {k: {"value": v, "limit": limits[k]}
                  for k, v in values.items()}
        return sound and all(v <= limits[k] for k, v in values.items()), \
            checks

    correct, checks = judge("served")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(mem)}
    out = {"correct": bool(correct), "attempted": attempted, "failed": failed}
    if trace:
        out["metrics"] = per_layer(bench, workload, readings)
        device["busy_s"] = readings.trace["busy_s"]
        device["window_s"] = readings.trace["window_s"]
        out["device"] = device
        out["breakdown"] = {
            "device_ops": [[k, v] for k, v in
                           list(readings.trace["programs"].items())[:10]],
            "idle_gaps": [[k, v] for k, v in readings.trace["idle_gaps"]]}
    else:
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        out["metrics"] = {k: {"value": v, "unit": units[k]}
                          for k, v in metrics.items()
                          if k in units and _applies(
                              next(m for m in bench["end_to_end"]
                                   if m["name"] == k), workload)}
        out["device"] = device
    if controls:
        out["logit_rms"] = {m: ref[m]["rms"] for m in ("served", *controls)
                            if m in ref}
        out["controls"] = {}
        for q in controls:
            ok, c = judge(q)
            out["controls"][q] = {"correct": bool(ok), "checks": c}
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return out


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


class Readings:
    """What the per-layer readers read: the traced wave's counters, its
    requests, its reduced device trace and, by program, the device seconds
    and collective seconds of its chips (`devtrace.collectives`)."""

    def __init__(self, conf, wave, k, trace, peaks, chips, collectives=None):
        self.conf, self.k, self.trace, self.peaks = conf, k, trace, peaks
        self.chips, self.collectives = chips, collectives
        self.counts = load_module(conf["counts"])
        self.slots = conf["serving"]["slots"]
        self.whole = wave["whole"]
        self.interval_s = wave["seconds"]
        self.engine = wave["engine"]
        self.scheduler = wave["scheduler"]
        self.ledger = wave["ledger"]
        self.retrieval_s = wave["retrieval_s"]
        self.requests = [(len(r.prompt), r.shared_len, len(r.out),
                          r.accepted_tokens) for r in wave["requests"]]


def per_layer(bench: dict, workload: str, readings: Readings) -> dict:
    out = {}
    for m in bench["per_layer"]:
        if not _applies(m, workload):
            continue
        value = load_metric(m["name"]).read(readings)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv: list, t_start: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="one run of a benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   t_start=t_start)
    print(json.dumps(out), flush=True)
    return 0
