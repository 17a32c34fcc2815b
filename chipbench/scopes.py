#!/usr/bin/env python3
"""Device seconds per `jax.named_scope` inside each program of a traced
wave. A tool for choosing the next optimisation; no metric reads it.

    python3 chipbench/scopes.py --workload qwen2.5-3b.swde --seed 7 \
        --seconds 51 --out chiprun_out/scopes.json

runs one traced run of the cell exactly as `run.py --trace 1` does and
writes, beside its result object (`result`), the device seconds and bytes
of each scope of each program (`scopes`: program -> scope -> [seconds,
bytes accessed]) and every idle gap of the wave by the innermost host event
covering it (`idle_gaps`, not only `breakdown`'s ten longest).

How a scope is read: on `/device:TPU:0` of the profiler's XSpace, each leaf
event of the line `XLA Ops` (an event no later event starts inside: a
while or conditional op spans the ops of its body) is put down to the
`XLA Modules` event (program) that covers its start and to the first of
SCOPES found among the `/`-separated parts of its `tf_op` stat, the op's
name path. An op under none of them counts as `other:<hlo_category>`
(`data formatting` for copies and transposes, `loop fusion`, ...).
Seconds are summed event durations; bytes are the op's `bytes_accessed`.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is measured from here, as in run.py

import bisect  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# the named scopes of `models/` and `models/cache_ops.py`
SCOPES = ("kv_gather", "kv_scatter", "qkv_proj", "attention", "out_proj",
          "mlp", "lm_head")
DEVICE_PLANE = "/device:TPU:0"


def _xspace_class():
    """The profiler's XSpace message, declared here field for field
    (tsl/profiler/protobuf/xplane.proto) with the fields this tool reads,
    so that it needs protobuf alone."""
    from google.protobuf import descriptor_pb2, descriptor_pool, \
        message_factory
    F = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(name="chipbench_xplane.proto",
                                             package="chipbench_xplane")
    O, R = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    I64, U64, DBL, STR, BYT, MSG = (F.TYPE_INT64, F.TYPE_UINT64,
                                   F.TYPE_DOUBLE, F.TYPE_STRING,
                                   F.TYPE_BYTES, F.TYPE_MESSAGE)

    def msg(name, fields):
        m = fdp.message_type.add(name=name)
        for fname, num, typ, label, tname in fields:
            f = m.field.add(name=fname, number=num, type=typ, label=label)
            if tname:
                f.type_name = f".chipbench_xplane.{tname}"
    msg("XStat", [("metadata_id", 1, I64, O, None),
                  ("double_value", 2, DBL, O, None),
                  ("uint64_value", 3, U64, O, None),
                  ("int64_value", 4, I64, O, None),
                  ("str_value", 5, STR, O, None),
                  ("bytes_value", 6, BYT, O, None),
                  ("ref_value", 7, U64, O, None)])
    msg("XEvent", [("metadata_id", 1, I64, O, None),
                   ("offset_ps", 2, I64, O, None),
                   ("duration_ps", 3, I64, O, None),
                   ("stats", 4, MSG, R, "XStat")])
    msg("XLine", [("id", 1, I64, O, None), ("name", 2, STR, O, None),
                  ("timestamp_ns", 3, I64, O, None),
                  ("events", 4, MSG, R, "XEvent")])
    msg("XEventMetadata", [("id", 1, I64, O, None), ("name", 2, STR, O, None),
                           ("display_name", 4, STR, O, None),
                           ("stats", 5, MSG, R, "XStat")])
    msg("XStatMetadata", [("id", 1, I64, O, None), ("name", 2, STR, O, None)])
    msg("EventMetadataEntry", [("key", 1, I64, O, None),
                               ("value", 2, MSG, O, "XEventMetadata")])
    msg("StatMetadataEntry", [("key", 1, I64, O, None),
                              ("value", 2, MSG, O, "XStatMetadata")])
    msg("XPlane", [("id", 1, I64, O, None), ("name", 2, STR, O, None),
                   ("lines", 3, MSG, R, "XLine"),
                   ("event_metadata", 4, MSG, R, "EventMetadataEntry"),
                   ("stat_metadata", 5, MSG, R, "StatMetadataEntry")])
    msg("XSpace", [("planes", 1, MSG, R, "XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench_xplane.XSpace"))


def _scope(op_name: str):
    parts = op_name.split("/")
    return next((s for s in SCOPES if s in parts), None)


def _program(name: str) -> str:
    """`jit_prefill_chunk(1234)` -> `jit_prefill_chunk`."""
    return name.split("(")[0]


def scope_table(data: bytes) -> dict:
    """Program -> scope -> [device seconds, bytes accessed] of a serialized
    XSpace; empty where it has no DEVICE_PLANE."""
    space = _xspace_class()()
    space.ParseFromString(data)
    plane = next((p for p in space.planes if p.name == DEVICE_PLANE), None)
    if plane is None:
        return {}
    stat_name = {e.key: e.value.name for e in plane.stat_metadata}
    ops = {}     # event metadata id -> (name, bytes, tf_op, hlo_category)
    for e in plane.event_metadata:
        op, nbytes, cat = "", 0, "?"
        for st in e.value.stats:
            k = stat_name.get(st.metadata_id)
            if k == "tf_op":
                op = (st.str_value or stat_name.get(st.ref_value, "")
                      ).rstrip(":")
            elif k == "bytes_accessed":
                nbytes = st.int64_value or st.uint64_value
            elif k == "hlo_category":
                cat = st.str_value or stat_name.get(st.ref_value, "?")
        ops[e.key] = (e.value.name, nbytes, op, cat)
    lines = {ln.name: ln for ln in plane.lines}
    if "XLA Modules" not in lines or "XLA Ops" not in lines:
        return {}
    ln = lines["XLA Modules"]
    mods = sorted((ln.timestamp_ns * 1000 + ev.offset_ps,
                   ln.timestamp_ns * 1000 + ev.offset_ps + ev.duration_ps,
                   _program(ops[ev.metadata_id][0])) for ev in ln.events)
    starts = [m[0] for m in mods]
    ln = lines["XLA Ops"]
    evs = sorted((ln.timestamp_ns * 1000 + ev.offset_ps, ev.duration_ps,
                  ev.metadata_id) for ev in ln.events)
    table = collections.defaultdict(lambda: collections.defaultdict(
        lambda: [0.0, 0]))
    for j, (s, dur, mid) in enumerate(evs):
        if j + 1 < len(evs) and evs[j + 1][0] < s + dur:
            continue                        # not a leaf
        i = bisect.bisect_right(starts, s) - 1
        prog = mods[i][2] if i >= 0 and mods[i][1] >= s else "?"
        _, nbytes, op, cat = ops[mid]
        cell = table[prog][_scope(op) or f"other:{cat}"]
        cell[0] += dur / 1e12
        cell[1] += nbytes
    return {p: dict(t) for p, t in table.items()}


def main(argv: list) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root / "src"), str(root)]
    from chipbench import devtrace, harness

    read = {}
    load = devtrace.load

    def load_and_read(path):
        read["scopes"] = scope_table(Path(path).read_bytes())
        return load(path)
    devtrace.load = load_and_read
    devtrace.TOP = None                     # every idle gap
    result = harness.run_cell(args.workload, args.seed, args.seconds, True,
                              t_start=T_START)
    out = {"result": result, "scopes": read.get("scopes", {}),
           "idle_gaps": result.get("breakdown", {}).get("idle_gaps", [])}
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(out))
    print(json.dumps({k: result[k] for k in ("correct", "metrics")}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
