"""End-to-end driver (deliverable (b)): QUEST query execution where every
extraction runs through the REAL JAX serving engine (prefill + batched
decode with KV caches) — the paper's LLM substrate, not a mock.

    PYTHONPATH=src python examples/analytics_serving.py [--arch qwen2.5-3b]

Two analytics queries run *concurrently* through one Session multiplexed
over one serving engine: their document coroutines feed the same
continuous-batching rounds (shared `engine.run()` calls, shared prefix-KV
groups) and the second query reuses the first's sampling investment, so
its sampling token column is zero. Decode runs speculatively by default
(`spec_decode="prompt_lookup"`, DESIGN.md §14): n-gram drafts from each
request's own context are verified in batched chunks, emitting several
tokens per target invocation at byte-identical output — the acceptance
rate and decode steps saved are printed with the engine stats.

Uses the arch's reduced (smoke) config so it runs on CPU; `--full` serves
the published width (bf16 params) on whatever accelerator JAX finds.
`--mesh-shape 1x2` serves one column-parallel sharded engine on a device mesh
(DESIGN.md §15; on a CPU host that many CPU devices are made) and
`--replicas 2` runs data-parallel engines behind one shared admission
queue — rows are byte-identical either way. `--tenants N [--qps R]`
routes every extraction through the async admission tier (DESIGN.md §16):
each query runs as its own tenant under weighted fair-share scheduling
with page-headroom backpressure, and per-tenant token/latency accounting
prints at the end. Compilations persist across runs through
`launch/compile_cache.py` (`JAX_COMPILATION_CACHE_DIR`, else
`<checkout>/.jax_cache`).
"""
import argparse
import time

import jax

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.core import Filter, Query, Session, conj
from repro.data import lm_data
from repro.data.corpus import make_swde_corpus
from repro.extract.served import ServedExtractor
from repro.index.retriever import TwoLevelRetriever
from repro.launch.compile_cache import enable_compilation_cache
from repro.launch.mesh import make_serving_mesh, parse_mesh_shape
from repro.models import init_params
from repro.serving.engine import ServingEngine
from repro.serving.frontend import ServingFrontend
from repro.serving.replicas import ReplicaGroup


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b", choices=ARCH_IDS)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=None,
                    help="cross-document extraction batch (default: slots)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable shared-prefix KV reuse (DESIGN.md §10)")
    ap.add_argument("--spec-decode", default="prompt_lookup",
                    choices=["off", "prompt_lookup"],
                    help="speculative decoding drafter (DESIGN.md §14)")
    ap.add_argument("--mesh-shape", default=None,
                    help="serve on a (data, model) device mesh, e.g. 1x2 "
                         "(DESIGN.md §15; on a CPU host, virtual devices)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="data-parallel engine replicas behind one shared "
                         "queue (DESIGN.md §15)")
    ap.add_argument("--tenants", type=int, default=0,
                    help="route extraction through the async admission tier "
                         "with N tenants on weighted fair-share scheduling "
                         "(DESIGN.md §16); 0 = direct engine submission")
    ap.add_argument("--qps", type=float, default=0.0,
                    help="with --tenants: stagger query arrivals at this "
                         "rate instead of submitting all at once")
    args = ap.parse_args()
    if args.mesh_shape is not None:
        # CPU hosts get as many (virtual) CPU devices as the mesh needs; the
        # option touches only the CPU backend and must precede its start
        n_data, n_model = parse_mesh_shape(args.mesh_shape)
        jax.config.update("jax_num_cpu_devices", n_data * n_model)
    enable_compilation_cache()

    cfg = (get_config if args.full else get_smoke_config)(args.arch)
    cfg = cfg.replace(vocab_size=max(cfg.vocab_size, lm_data.VOCAB))
    print(f"serving {cfg.name} ({cfg.family}), d_model={cfg.d_model}, "
          f"layers={cfg.num_layers}")
    params = init_params(cfg, jax.random.PRNGKey(0))
    mesh = None
    if args.mesh_shape is not None:
        mesh = make_serving_mesh(parse_mesh_shape(args.mesh_shape))
        print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")
    if args.replicas > 1:
        engine = ReplicaGroup(cfg, params, replicas=args.replicas,
                              slots=args.slots, max_len=1024,
                              prefix_cache=not args.no_prefix_cache,
                              spec_decode=args.spec_decode, mesh=mesh)
        print(f"{args.replicas} engine replicas behind one shared queue")
    else:
        engine = ServingEngine(cfg, params, slots=args.slots, max_len=1024,
                               prefix_cache=not args.no_prefix_cache,
                               spec_decode=args.spec_decode, mesh=mesh)

    frontend = None
    if args.tenants > 0:
        frontend = ServingFrontend(engine, max_prefill_chunks=2)
        print(f"admission tier: {args.tenants} tenants, weighted fair share")

    corpus = make_swde_corpus()
    retriever = TwoLevelRetriever(corpus)
    # longer generations give the prompt-lookup drafter its regime (the
    # n-gram matcher accelerates repeated/copied spans mid-output)
    extractor = ServedExtractor(corpus, engine, max_new=24,
                                frontend=frontend)
    batch = args.batch_size if args.batch_size is not None else args.slots
    session = Session(retriever, extractor, sample_rate=0.03,
                      batch_size=batch)

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
    p1, p2 = session.prepare(q1), session.prepare(q2)
    for p in (p1, p2):
        print("\n" + p.explain_text())

    t0 = time.time()
    if args.tenants > 0:
        # each query runs as its own tenant (round-robin); --qps staggers
        # arrivals like a live workload instead of one submit burst
        handles = []
        for i, p in enumerate((p1, p2)):
            if args.qps > 0 and i:
                time.sleep(1.0 / args.qps)
            handles.append(p.submit(tenant=f"tenant-{i % args.tenants}"))
        h1, h2 = handles
    else:
        h1, h2 = p1.submit(), p2.submit()  # both in flight, shared rounds
    session.drain()
    dt = time.time() - t0
    r1, r2 = h1.result(), h2.result()

    for name, r in (("q1", r1), ("q2", r2)):
        print(f"\n{name}: {len(r.rows)} rows "
              f"(sampling tokens {r.ledger.per_phase.get('sampling', 0)}, "
              f"reused: {r.meta['sampling_reused']['universities']})")
        for row in r.rows[:10]:
            print("  ", row["universities.university_name"])
    print(f"\nboth queries in {dt:.1f}s over one engine")
    es = engine.stats
    if args.spec_decode != "off":
        acc = es["accepted_tokens"] / max(es["draft_tokens"], 1)
        print(f"speculative decode ({args.spec_decode}): "
              f"{es['draft_tokens']} drafted, {es['accepted_tokens']} "
              f"accepted ({acc:.1%}), {es['decode_steps_saved']} decode "
              f"steps saved over {es['spec_rounds']} verify rounds")
    print("session ledger:", session.ledger.snapshot())
    print("serving engine stats:", engine.stats)
    print("served extractor:", extractor.stats)
    print("batch scheduler:", session.scheduler.stats.snapshot())
    if frontend is not None:
        print("admission tier:", frontend.stats)
        for tenant, snap in sorted(frontend.tenant_snapshot().items()):
            print(f"  {tenant}: {snap}")
        for tenant, snap in session.tenant_costs().items():
            print(f"  {tenant} tokens: in={snap['input_tokens']} "
                  f"out={snap['output_tokens']} calls={snap['llm_calls']}")


if __name__ == "__main__":
    main()
