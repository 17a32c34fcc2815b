"""The profiler trace of a traced run, reduced to what the per-layer metrics
and the result's `breakdown` read.

`jax.profiler` writes an XSpace (`*.xplane.pb`). On a TPU each chip is a
plane named `/device:TPU:<n>`, whose line `XLA Modules` holds one event per
execution of a compiled program (named after the jitted function); the
host's threads are lines of the plane `/host:CPU`, and the benchmark marks
its own calls into each layer there with `jax.profiler.TraceAnnotation`
(names starting `chipbench.`). Busy time is the union of the program
executions of a chip, averaged over the chips used; an idle gap is named
after the innermost host event on the benchmark's thread that covers its
middle. The line `XLA Ops` of a chip holds one event per execution of an
HLO op, named by the op's HLO text (`%all-gather-start.3 = ...
all-gather-start(...)`); `collectives` reads the collective ops there.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

import numpy as np

WAVE_MARK = "chipbench.wave"
TOP = 10
# HLO opcodes of the collectives, each also as its async `-start` and
# `-done`
COLLECTIVE = re.compile(r"(all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|all-to-all)(-start|-done)?")


def xplane_file(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"the profiler wrote no trace under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str):
    from jax._src.profiler import ProfileData
    return ProfileData.from_file(path)


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge (start, end) rows into disjoint sorted intervals."""
    if not len(iv):
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.float64)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def _program_name(name: str) -> str:
    """`jit_fn(1234)` -> `jit_fn`: one name per compiled function."""
    return re.sub(r"\(\d+\)$", "", name)


def _wave(pd) -> tuple:
    """The host line that holds the span named WAVE_MARK, and the span's
    (start, end); (None, None) where the trace has none."""
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WAVE_MARK:
                    return line, (ev.start_ns, ev.end_ns)
    return None, None


def _chips(pd, chips: int) -> list:
    return sorted((p for p in pd.planes
                   if re.fullmatch(r"/device:TPU:\d+", p.name)),
                  key=lambda p: p.name)[:chips]


def reduce(pd, chips: int) -> dict:
    """Busy seconds per chip, device seconds per program, and the longest
    idle gaps of a trace, over the benchmark's marked wave (the span named
    WAVE_MARK)."""
    host_line, window = _wave(pd)
    devices = _chips(pd, chips)
    spans, programs = [], {}
    for plane in devices:
        iv = []
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            for ev in line.events:
                iv.append((ev.start_ns, ev.end_ns))
                key = _program_name(ev.name)
                programs[key] = programs.get(key, 0.0) + ev.duration_ns
        spans.append(np.asarray(iv, np.float64).reshape(-1, 2))
    if not devices or not any(len(s) for s in spans):
        return {"busy_s": 0.0, "window_s": 0.0, "programs": {},
                "idle_gaps": []}
    if window is None:
        raise ValueError(f"the trace has no {WAVE_MARK} span")
    lo, hi = window
    busy = [_union(_clip(s, lo, hi)) for s in spans]
    busy_ns = float(np.mean([(b[:, 1] - b[:, 0]).sum() for b in busy]))
    gaps = _gaps(busy[0], lo, hi)
    names = _host_names(host_line, gaps)
    by_name: dict = {}
    for (s, e), name in zip(gaps, names):
        by_name[name] = by_name.get(name, 0.0) + float(e - s) / 1e9
    top_programs = sorted(programs.items(), key=lambda kv: -kv[1])
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": (hi - lo) / 1e9,
        "programs": {k: v / 1e9 / len(devices) for k, v in top_programs},
        "idle_gaps": sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP],
    }


def _opcode(text: str) -> str:
    """The opcode of an op event named by its HLO text, `%name = shape
    opcode(operands), ...`; the name itself where there is no text."""
    name, eq, rest = text.partition(" = ")
    if not eq:
        return name.lstrip("%")
    depth = 0
    for i, ch in enumerate(rest):          # skip the shape, maybe a tuple
        depth += (ch == "(") - (ch == ")")
        if ch == " " and depth == 0:
            return rest[i + 1:].split("(", 1)[0]
    return ""


def is_collective(text: str) -> bool:
    """Whether an op event is a collective: by its opcode, or by its name
    where XLA wraps one in another op (an async or fusion wrapper)."""
    name = text.partition(" = ")[0].lstrip("%")
    return bool(COLLECTIVE.fullmatch(_opcode(text))
                or COLLECTIVE.match(name))


def collectives(pd, chips: int) -> dict:
    """Program -> [device seconds, seconds of its collective ops] over the
    benchmark's marked wave, averaged over the chips used.

    A program execution counts where it starts inside the wave. Its ops are
    the leaf events of the chip's `XLA Ops` line that start inside it (an
    event that the next event starts inside, as a while loop spans the ops
    of its body, is no leaf; the line holds its events in order of their
    start), so they never overlap one another: the seconds of its
    collective ops (`is_collective`, an async start and its done each on
    their own) are seconds in which the chip did nothing else. An async
    collective's transfer between its start and its done, under other ops,
    counts nothing. Empty where the trace has no chip plane."""
    _, window = _wave(pd)
    devices = _chips(pd, chips)
    out: dict = {}
    if window is None:
        return out
    lo, hi = window
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        if "XLA Modules" not in lines or "XLA Ops" not in lines:
            continue
        mods = sorted((ev.start_ns, ev.end_ns, _program_name(ev.name))
                      for ev in lines["XLA Modules"].events
                      if lo <= ev.start_ns < hi)
        starts = [m[0] for m in mods]
        for s, e, name in mods:
            out.setdefault(name, [0.0, 0.0])[0] += (e - s) / 1e9

        def count(ev):
            """Puts a leaf op down to the program that covers its start."""
            s = ev.start_ns
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and mods[i][1] >= s and is_collective(ev.name):
                out[mods[i][2]][1] += ev.duration_ns / 1e9

        prev = None
        for ev in lines["XLA Ops"].events:
            if prev is not None and ev.start_ns >= prev.end_ns:
                count(prev)
            prev = ev if lo <= ev.start_ns < hi else None
        if prev is not None:
            count(prev)
    n = max(len(devices), 1)
    return {k: [v[0] / n, v[1] / n] for k, v in out.items()}


def _gaps(busy: np.ndarray, lo: float, hi: float) -> list:
    edges = [lo] + list(busy.ravel()) + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def _host_names(line, gaps: list) -> list:
    """For each gap, the innermost benchmark or JAX host event on the
    benchmark's thread that covers its middle. Events of one thread nest,
    so the innermost is the latest-starting one that still covers it."""
    evs = sorted(((ev.start_ns, ev.end_ns, ev.name) for ev in line.events
                  if ev.name != WAVE_MARK), key=lambda t: t[0])
    starts = np.asarray([e[0] for e in evs], np.float64)
    out = []
    for s, e in gaps:
        mid = (s + e) / 2
        name = "host, unmarked"
        first = int(np.searchsorted(starts, mid, "right")) - 1
        for i in range(first, max(first - 5000, -1), -1):
            if evs[i][1] >= mid:
                name = _program_name(evs[i][2])
                break
        out.append(name)
    return out
