"""Serving mesh (`distributed/sharding.py` SERVING_ROLES, the engine's
`mesh=`): the share of the engine phase programs' device time in which a
chip did nothing but a collective op (an all-gather, all-reduce,
reduce-scatter, collective-permute or all-to-all, with their async start
and done), averaged over the chips, from the traced wave's `XLA Ops` lines
(`devtrace.collectives`). Moves queries_per_min."""

# the engine's jitted phases (`serving/engine.py` `_jit_phase`)
PHASES = ("jit_prefill_chunk", "jit_verify_round", "jit_paged_decode",
          "jit_slab_prefill", "jit_slab_decode", "jit_slab_verify")


def read(r):
    runs = [v for k, v in (r.collectives or {}).items() if k in PHASES]
    device = sum(s for s, _ in runs)
    if not device:
        return None
    return 100.0 * sum(c for _, c in runs) / device
