#!/usr/bin/env python3
"""Smoke run of the served analytics path on a TPU, at full qwen2.5-3b width.

    python3 chip_smoke.py [--seed 2]      # one chip
    python3 chip_smoke.py --chips 4       # 2x2 (data, model) mesh vs one chip

Everything runs in this one process, in order, and any failure exits
non-zero:

  1. JAX's first device must be a TPU (no CPU path); the device kind and
     count are printed.
  2. The persistent compile cache is turned on (`launch/compile_cache.py`).
  3. Retrieval: every segment of the three seeded corpora in one
     `ExactIndex` (thousands of rows, so the device ranking `l2_rank` runs),
     `search` and `range_search_many` checked against a float64 numpy
     ranking, equal distances in index order.
  4. Served analytics: the two concurrent `universities` queries of
     `examples/analytics_serving.py` over `make_swde_corpus(seed)`, through
     Session -> ServedExtractor -> ServingEngine(paged KV, prefix cache,
     prompt-lookup speculation) with qwen2.5-3b `CONFIG` in bf16, params
     from `PRNGKey(seed)`. Rows must equal the same session run with the
     exact `OracleExtractor`; no request may fail or be truncated.
  5. Numerics: the engine's first-token logits for one prompt (paged
     chunked prefill, spliced onto a cached prefix) against the model's
     one-shot `forward`, within LOGIT_TOL.

With `--chips 4` only phase 4 runs: once on a one-chip engine and once on a
2x2 (data, model) mesh engine, whose rows and decoded tokens must be
identical.

Wall times printed are smoke timings, not metrics. The last line of
standard output is one JSON object:
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

RANK_TOL = 1e-5        # float32 device distance vs float64 numpy distance
# max |engine - forward| over the vocabulary. bf16 keeps 8 significant
# bits, so one rounding of a logit near 4 moves it by up to 2**-6; chunked
# prefill over cached pages and the one-shot forward round 36 layers of
# sums in different orders and drift by several such steps (0.12 to 0.17
# at seed 2 on TPU v5e). A position or cache bug moves logits by their own
# scale.
LOGIT_TOL = 0.25
SLOTS, MAX_LEN, MAX_NEW = 8, 1024, 24


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, detail="") -> None:
    """An assert that `python -O` cannot strip."""
    if not ok:
        raise AssertionError(detail)


class CompileCounter:
    """Counts XLA compilations (persistent-cache loads included) and their
    seconds through jax.monitoring."""

    def __init__(self):
        import jax.monitoring as mon
        self.n = self.hits = 0
        self.secs = 0.0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def since(self, mark):
        n, hits, secs = mark
        return (f"{self.n - n} compilations ({self.hits - hits} from the "
                f"persistent cache), {self.secs - secs:.1f} s compiling")

    def mark(self):
        return self.n, self.hits, self.secs


# ------------------------------------------------------------ retrieval ---


def check_ranking(got_ids, got_d, ref_d):
    """One query's device ranking against float64 numpy distances `ref_d`
    to every row. Returns (near-tie swaps, exact ties checked)."""
    import numpy as np
    got_ids, got_d = np.asarray(got_ids, int), np.asarray(got_d)
    want = np.argsort(ref_d, kind="stable")[:len(got_ids)]
    np.testing.assert_allclose(got_d, ref_d[got_ids], atol=RANK_TOL)
    np.testing.assert_allclose(got_d, ref_d[want], atol=RANK_TOL)
    swapped = got_ids != want
    # only rows whose distances agree within float32 resolution may trade
    # places; exactly equal distances must come in index order
    check(np.all(np.abs(ref_d[got_ids[swapped]] - ref_d[want[swapped]])
                 < RANK_TOL), (got_ids[swapped], want[swapped]))
    tie = ref_d[got_ids[:-1]] == ref_d[got_ids[1:]]
    check(np.all(got_ids[:-1][tie] < got_ids[1:][tie]))
    return int(swapped.sum()), int(tie.sum())


def retrieval_phase(seed: int) -> None:
    import numpy as np
    from repro.data.corpus import (make_legal_corpus, make_swde_corpus,
                                   make_wiki_corpus)
    from repro.index.embedder import HashedEmbedder
    from repro.index.segmenter import segment_document
    from repro.index.vector_index import ExactIndex, l2_rank_device

    corpora = [make(seed) for make in (make_wiki_corpus, make_legal_corpus,
                                       make_swde_corpus)]
    splitter = HashedEmbedder()
    texts = [s.text for c in corpora for doc_id, doc in c.docs.items()
             for s in segment_document(doc_id, doc.text, splitter)]
    emb = HashedEmbedder().fit(texts)
    rows = emb.embed(texts)
    queries = emb.embed([f"{attr} {c.attr_description(table, attr)}"
                         for c in corpora for table, attrs in
                         sorted(c.attr_specs.items()) for attr in sorted(attrs)])
    index = ExactIndex(rows)
    n_dup = len(rows) - len(np.unique(rows, axis=0))
    log(f"retrieval: {len(index)} segment rows ({n_dup} exact duplicates), "
        f"{len(queries)} queries")
    check(len(index) >= 256, len(index))
    ref = np.sqrt(((queries.astype(np.float64)[:, None]
                    - rows.astype(np.float64)[None]) ** 2).sum(-1))

    before = l2_rank_device._cache_size()
    swaps = ties = 0
    for q, (ids, d) in zip(range(len(queries)), index.search(queries, 10)):
        s, t = check_ranking(ids, d, ref[q])
        swaps, ties = swaps + s, ties + t
    # one threshold per query, between two well-separated numpy distances
    taus, keep = [], []
    for q in range(len(queries)):
        d = np.sort(ref[q])
        r = next(r for r in range(40, len(d) - 1) if d[r + 1] - d[r] > 1e-3)
        taus.append((d[r] + d[r + 1]) / 2)
        keep.append(r + 1)
    for q, (ids, d) in enumerate(index.range_search_many(queries, taus)):
        check(len(ids) == keep[q], (q, len(ids), keep[q]))
        s, t = check_ranking(ids, d, ref[q])
        swaps, ties = swaps + s, ties + t
    check(l2_rank_device._cache_size() > before, "device ranking never ran")
    log(f"retrieval: search(k=10) and range_search_many match numpy on "
        f"{jax_platform()}; {ties} equal-distance pairs in index order, "
        f"{swaps} near-tie swaps (|d| diff < {RANK_TOL})")


def jax_platform() -> str:
    import jax
    return jax.devices()[0].platform


# --------------------------------------------------------------- served ---


def queries():
    from repro.core import Filter, Query, conj
    q1 = Query(
        tables=["universities"],
        select=[("universities", "university_name")],
        where=conj(Filter("tuition", "<", 20000, table="universities"),
                   Filter("enrollment", ">", 30000, table="universities")),
    )
    q2 = Query(
        tables=["universities"],
        select=[("universities", "university_name")],
        where=Filter("enrollment", ">", 45000, table="universities"),
    )
    return q1, q2


def run_session(session) -> list:
    handles = [session.prepare(q).submit() for q in queries()]
    session.drain()
    return [h.result().rows for h in handles]


def oracle_rows(seed: int) -> list:
    from repro.core import Session
    from repro.data.corpus import make_swde_corpus
    from repro.extract import OracleExtractor
    from repro.index.retriever import TwoLevelRetriever
    corpus = make_swde_corpus(seed)
    session = Session(TwoLevelRetriever(corpus),
                      OracleExtractor(corpus, noisy=False),
                      sample_rate=0.03, batch_size=SLOTS)
    return run_session(session)


def served_phase(cfg, params, seed: int, counter, *, mesh=None,
                 label: str = "one chip"):
    """Returns (rows, decoded tokens by request id, engine, extractor)."""
    from repro.core import Session
    from repro.data.corpus import make_swde_corpus
    from repro.extract.served import ServedExtractor
    from repro.index.retriever import TwoLevelRetriever
    from repro.serving.engine import ServingEngine

    t0, mark = time.time(), counter.mark()
    corpus = make_swde_corpus(seed)
    engine = ServingEngine(cfg, params, slots=SLOTS, max_len=MAX_LEN,
                           kv_layout="paged", prefix_cache=True,
                           spec_decode="prompt_lookup", mesh=mesh)
    extractor = ServedExtractor(corpus, engine, max_new=MAX_NEW)
    session = Session(TwoLevelRetriever(corpus), extractor,
                      sample_rate=0.03, batch_size=SLOTS)
    rows = run_session(session)
    es, xs = engine.stats, extractor.stats
    log(f"served ({label}): {[len(r) for r in rows]} rows, "
        f"{xs.requests} requests, {es['decode_steps']} decode steps "
        f"({es['spec_rounds']} verify rounds, {es['accepted_tokens']}/"
        f"{es['draft_tokens']} drafts accepted), {es['prefill_chunks']} "
        f"prefill chunks, {es['prefix_hits']} prefix hits, truncations "
        f"{es['truncations']}, failed {len(engine.failed)}")
    log(f"served ({label}): parse fallbacks {xs.parse_fallbacks}/{xs.parses} "
        f"({xs.parse_fallbacks / max(xs.parses, 1):.1%}) — decoded answers "
        f"that did not parse, valued by the corpus oracle (DESIGN.md §8.1)")
    log(f"served ({label}): {counter.since(mark)}; smoke wall "
        f"{time.time() - t0:.1f} s (not a metric)")
    check(es["decode_steps"] > 0, es)
    check(es["truncations"] == 0, es)
    check(not engine.failed, engine.failed)
    tokens = {rid: list(r.out) for rid, r in engine.finished.items()}
    return rows, tokens, engine, extractor


def logits_check(cfg, engine, extractor, seed: int) -> None:
    """First-token logits of one prompt through the engine's paged chunked
    prefill against the one-shot `forward` of the same params."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.data import lm_data
    from repro.models import forward
    from repro.serving.engine import Request

    corpus = extractor.corpus
    doc_id = sorted(d for d, doc in corpus.docs.items()
                    if doc.table == "universities")[0]
    text = (extractor._prompt_prefix(doc_id, "tuition")
            + corpus.docs[doc_id].text[:300])
    prompt = lm_data.encode(text)
    slot = engine._free_slot()
    gen = engine._insert_paged_co(slot, Request(rid=-1, prompt=prompt))
    try:
        while True:
            next(gen)
    except StopIteration as stop:
        got = np.asarray(stop.value[0, -1], np.float32)
    engine._free_slot_pages(slot)
    fwd = jax.jit(lambda p, t: forward(cfg, p, {"tokens": t})[0][0, -1])
    want = np.asarray(fwd(engine.params, jnp.asarray([prompt], jnp.int32)),
                      np.float32)
    diff = np.abs(got - want)
    err = float(diff.max())
    top2 = np.sort(want)[-2:]
    log(f"numerics: {len(prompt)}-token prompt, engine vs forward logits "
        f"max |diff| {err:.4f} (tolerance {LOGIT_TOL}), mean |diff| "
        f"{float(diff.mean()):.4f}, max |logit| {float(np.abs(want).max()):.3f}"
        f", argmax {int(got.argmax())} vs {int(want.argmax())} (forward's "
        f"top-2 gap {float(top2[1] - top2[0]):.4f})")
    check(np.isfinite(got).all() and np.isfinite(want).all())
    check(err <= LOGIT_TOL, err)


# ----------------------------------------------------------------- main ---


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=2,
                    help="corpora and params seed (SWDE's default corpus)")
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: the served phase on a 2x2 mesh vs one chip")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips}, found {len(devices)}")
    log(f"device: {devices[0].device_kind} x{len(devices)} "
        f"(jax {jax.__version__})")

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compilation_cache
    from repro.launch.mesh import make_serving_mesh
    from repro.models import init_params, param_count
    log(f"compile cache: {enable_compilation_cache()}")
    counter = CompileCounter()

    if args.chips == 1:
        t0 = time.time()
        retrieval_phase(args.seed)
        log(f"retrieval: smoke wall {time.time() - t0:.1f} s (not a metric)")

    t0 = time.time()
    cfg = get_config("qwen2.5-3b")
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    dtypes = sorted({str(a.dtype) for a in jax.tree.leaves(params)})
    log(f"model: {cfg.name} {cfg.num_layers}L d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} vocab={cfg.vocab_size}, "
        f"{param_count(params) / 1e9:.2f} B params in {dtypes}; init "
        f"{time.time() - t0:.1f} s")
    check(dtypes == ["bfloat16"], dtypes)

    t0 = time.time()
    want = oracle_rows(args.seed)
    log(f"oracle: {[len(r) for r in want]} rows, {time.time() - t0:.1f} s")

    rows, tokens, engine, extractor = served_phase(cfg, params, args.seed,
                                                   counter)
    check(rows == want, "served rows differ from the oracle's")
    log("served (one chip): rows equal the oracle's")

    if args.chips == 1:
        t0 = time.time()
        logits_check(cfg, engine, extractor, args.seed)
        log(f"numerics: smoke wall {time.time() - t0:.1f} s (not a metric)")
    else:
        del engine, extractor
        mesh = make_serving_mesh((2, 2))
        m_rows, m_tokens, m_engine, _ = served_phase(
            cfg, params, args.seed, counter, mesh=mesh, label="2x2 mesh")
        wq = m_engine.params["layers"]["attn"]["wq"]
        pool = m_engine.alloc.pools["k"]
        for name, a in (("params wq", wq), ("pool k", pool)):
            log(f"mesh: {name} {a.shape} {a.sharding.spec} on "
                f"{len(a.sharding.device_set)} devices")
            check(len(a.sharding.device_set) == 4, a.sharding)
        check(m_rows == rows, "mesh rows differ from one chip's")
        check(m_tokens == tokens, "mesh decoded tokens differ from one chip's")
        log(f"mesh: rows and all {len(tokens)} requests' decoded tokens "
            f"identical to one chip")

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
