import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

# NOTE: the two lines above MUST precede every other import (jax locks the
# device count at first init). Hence no `from __future__ import annotations`.

DOC = """Multi-pod dry-run driver (deliverable (e)).

For every (architecture x input shape) cell, builds the abstract inputs
(`input_specs`, ShapeDtypeStruct only — no allocation), lowers and compiles
the corresponding step function (train_step / prefill / serve_step) on the
production mesh, and records memory_analysis / cost_analysis / per-device
collective bytes into an incremental JSON file consumed by EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --mesh single --all
  PYTHONPATH=src python -m repro.launch.dryrun --mesh multi --arch qwen3-32b --shape train_4k
  ... --probe 2  (reduced-depth unrolled probe for roofline extrapolation)
"""

import argparse
import json
import time
import traceback
from functools import partial
from pathlib import Path

import jax

from repro.configs import ARCH_IDS, get_config
from repro.configs.shapes import SHAPES, SHAPE_ORDER, applicable
from repro.distributed import sharding as sh
from repro.launch import flops as F
from repro.launch.hlo_analysis import (collective_bytes, cost_analysis_dict,
                                       memory_analysis_dict)
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (default_microbatch, default_opt_cfg,
                                input_specs)
from repro.models import decode_step, prefill
from repro.models import layers as mlayers
from repro.models.config import ModelConfig
from repro.training.train_step import make_train_step

RESULTS = Path(__file__).resolve().parents[3] / "benchmarks" / "dryrun_results.json"


def probe_config(cfg: ModelConfig, n: int) -> ModelConfig:
    """Reduced-depth config for unrolled cost probes (same widths)."""
    if cfg.family == "hybrid":
        return cfg.replace(num_layers=n * cfg.attn_every)
    if cfg.family == "encdec":
        return cfg.replace(num_layers=n, n_encoder_layers=n)
    if cfg.family == "moe" and cfg.first_dense_layers:
        return cfg.replace(num_layers=cfg.first_dense_layers + n)
    return cfg.replace(num_layers=n)


from repro.distributed.sharding import _ROLES as _BASE_ROLES

VARIANT_OVERRIDES = {
    # expert parallelism: expert weights over `data`, ff over `model`
    "ep_moe": {"w_gate": "f.t", "w_up": "f.t", "w_down": "ft."},
    # weight-stationary serving: drop FSDP (replicate over `data`), keep TP —
    # decode must move activations (tiny), not weights (huge)
    "serve_ws": {n: r.replace("f", ".") for n, r in _BASE_ROLES.items()},
    "serve_ws_seqdec": {n: r.replace("f", ".") for n, r in _BASE_ROLES.items()},
}


def build_lowered(arch: str, shape_name: str, mesh, *, probe: int = 0,
                  unroll: bool = False, dense_attn: bool = False,
                  variant: str = "baseline"):
    cfg = get_config(arch)
    if probe:
        cfg = probe_config(cfg, probe)
    shape = SHAPES[shape_name]
    mlayers.set_attention_impl("dense" if dense_attn else None)
    attn_impl = None
    if variant in ("seq_decode", "serve_ws_seqdec"):
        from repro.distributed.decode import make_seq_sharded_decode_attn
        attn_impl = make_seq_sharded_decode_attn(mesh)
    elif variant == "serve_ws2d_seqdec":
        from repro.distributed.decode import make_seq_sharded_decode_attn
        attn_impl = make_seq_sharded_decode_attn(mesh, axis=("data", "model"),
                                                 batch_axis=None)
    try:
        specs = input_specs(arch, shape_name, mesh, cfg=cfg,
                            shard_overrides=VARIANT_OVERRIDES.get(variant),
                            decode_layout="ws2d" if variant.startswith("serve_ws2d") else "default")
        constrain = (sh.make_constrain(mesh, shape.global_batch)
                     if not variant.startswith("serve_ws2d") else None)
        if shape.kind == "train":
            opt_cfg = specs["opt_cfg"]
            mb = 0 if probe else default_microbatch(cfg, shape, mesh)
            _, train_step = make_train_step(cfg, opt_cfg, remat=True,
                                            constrain=constrain, microbatch=mb,
                                            unroll=unroll)

            def fn(params, opt_state, batch):
                return train_step(params, opt_state, batch)

            lowered = jax.jit(fn, donate_argnums=(0, 1)).lower(
                specs["params"], specs["opt_state"], specs["batch"])
            meta = {"opt": opt_cfg.name, "microbatch": mb}
        elif shape.kind == "prefill":
            def fn(params, batch):
                return prefill(cfg, params, batch, shape.seq_len,
                               constrain=constrain, remat=False, unroll=unroll)

            lowered = jax.jit(fn).lower(specs["params"], specs["batch"])
            meta = {}
        else:
            def fn(params, token, cache):
                return decode_step(cfg, params, token, cache,
                                   constrain=constrain, unroll=unroll,
                                   attn_impl=attn_impl)

            lowered = jax.jit(fn, donate_argnums=(2,)).lower(
                specs["params"], specs["token"], specs["cache"])
            meta = {}
        return cfg, shape, lowered, meta
    finally:
        mlayers.set_attention_impl(None)


def run_cell(arch: str, shape_name: str, mesh_kind: str, *, probe: int = 0,
             unroll: bool = False, dense_attn: bool = False,
             variant: str = "baseline") -> dict:
    cfg_full = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = applicable(cfg_full, shape_name)
    key = f"{arch}|{shape_name}|{mesh_kind}" + (f"|probe{probe}" if probe else "")
    if variant != "baseline":
        key += f"|{variant}"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind, "probe": probe,
           "variant": variant}
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = reason
        return key, rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    try:
        cfg, shape, lowered, meta = build_lowered(
            arch, shape_name, mesh, probe=probe,
            unroll=unroll or bool(probe), dense_attn=dense_attn or bool(probe),
            variant=variant)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        rec.update({
            "status": "ok",
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "n_devices": mesh.devices.size,
            "memory": memory_analysis_dict(compiled),
            "cost": cost_analysis_dict(compiled),
            "collectives": collective_bytes(compiled.as_text()),
            **meta,
        })
        if not probe:
            rec["model_flops"] = F.model_flops(cfg, shape)
            rec["attention_flops"] = F.attention_flops(cfg, shape)
            rec["ssm_scan_flops"] = F.ssm_scan_flops(cfg, shape)
            rec["param_count"] = cfg.param_count()
            rec["param_count_active"] = cfg.param_count(active_only=True)
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    return key, rec


def load_results() -> dict:
    if RESULTS.exists():
        return json.loads(RESULTS.read_text())
    return {}


def save_result(key: str, rec: dict):
    res = load_results()
    res[key] = rec
    RESULTS.write_text(json.dumps(res, indent=1, default=float))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCH_IDS + [None])
    ap.add_argument("--shape", default=None, choices=SHAPE_ORDER + [None])
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--probe", type=int, default=0,
                    help="reduced depth (unrolled, dense-attn) cost probe")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default="baseline",
                    choices=["baseline", "seq_decode", "ep_moe", "serve_ws",
                             "serve_ws_seqdec", "serve_ws2d", "serve_ws2d_seqdec"])
    args = ap.parse_args()

    archs = [args.arch] if args.arch else ARCH_IDS
    shapes = [args.shape] if args.shape else SHAPE_ORDER
    existing = load_results()
    for arch in archs:
        for shape_name in shapes:
            key = f"{arch}|{shape_name}|{args.mesh}" + (
                f"|probe{args.probe}" if args.probe else "")
            if args.variant != "baseline":
                key += f"|{args.variant}"
            if not args.force and key in existing and \
                    existing[key].get("status") in ("ok", "skipped"):
                print(f"[skip-cached] {key}")
                continue
            print(f"[run] {key}", flush=True)
            k, rec = run_cell(arch, shape_name, args.mesh, probe=args.probe,
                              variant=args.variant)
            save_result(k, rec)
            st = rec["status"]
            extra = ""
            if st == "ok":
                mem = rec["memory"].get("temp_size_in_bytes", 0)
                extra = (f" compile={rec['compile_s']}s "
                         f"temp={mem/2**30:.2f}GiB "
                         f"flops={rec['cost'].get('flops', 0):.3e} "
                         f"coll={rec['collectives'].get('_total', 0)/2**20:.1f}MiB")
            elif st == "error":
                extra = " " + rec["error"][:200]
            print(f"[done] {key}: {st}{extra}", flush=True)


if __name__ == "__main__":
    main()
