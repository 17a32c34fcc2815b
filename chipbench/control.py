#!/usr/bin/env python3
"""The control of the model comparison, at a cell's own size and load.

    python3 chipbench/control.py --workload qwen2.5-3b.swde --seeds 1,2,3

For each seed, one run of the cell (`harness.run_cell`) whose window is one
whole wave: the program's readings, judged as a benchmark run judges them,
and the same run with the reference put in the program's place, computed
with its weights rounded to int8 (the control: one precision below the
served bfloat16) and to fp8 e4m3, each judged by the same limits. The
control has to come out not correct. One JSON line per seed on standard
output: `correct` and the compared numbers of the program and of each
control, and the root mean square logit distance of each. The benchmark's
own runs never run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench import harness  # noqa: E402

CONTROLS = ("int8", "fp8")


def _values(checks: dict) -> dict:
    return {k: c["value"] for k, c in checks.items()}


def readings(workload: str, seeds: list, *, require_tpu: bool = True,
             conf=None) -> list:
    out = []
    for seed in seeds:
        r = harness.run_cell(workload, seed, 0.0, False,
                             t_start=time.perf_counter(),
                             require_tpu=require_tpu, conf=conf,
                             controls=CONTROLS, one_wave=True)
        out.append({
            "seed": seed, "correct": r["correct"],
            "checks": _values(r["checks"]), "logit_rms": r["logit_rms"],
            "controls": {q: {"correct": c["correct"],
                             "checks": _values(c["checks"])}
                         for q, c in r["controls"].items()}})
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    args = ap.parse_args()
    for seed in (int(s) for s in args.seeds.split(",")):
        r, = readings(args.workload, [seed])
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
