"""The harness on a serving mesh, off the chip: a test-sized model
(`testdata/smoke.json`) on a (1, 4) mesh of four CPU devices, each run in a
child process started with the XLA flag that makes them. A sound run comes
out correct; one with the exchange between the chips left out, where the
column-parallel layers gather their activations, comes out not correct; the
weights drawn into the mesh's layout are those of a draw on one device, bit
for bit. The device-trace readers (rooflines, `collective_share`) read
nothing off the chip, whose trace has no chip plane: `test_chipbench_trace.py`
reads a recorded four-chip trace."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from chipbench import harness

ROOT = Path(__file__).resolve().parents[1]
CELL = "smoke-tp4.swde"                  # a 4-chip cell on a (1, 4) mesh

# the child's benchmark: BENCHMARK.json with a four-chip swde cell added
PRELUDE = f"""
import json, sys, time
from pathlib import Path
sys.path[:0] = [{str(ROOT / "src")!r}, {str(ROOT)!r}]
import jax
from chipbench import harness
CONF = json.loads(Path({str(ROOT / "chipbench/testdata/smoke.json")!r})
                  .read_text())
CONF["serving"] = dict(CONF["serving"], mesh=[1, 4])
PEAKS = {{"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
BENCH = harness.load_benchmark()
BENCH["workloads"].append({{"name": {CELL!r}, "config": "smoke",
                            "traffic": "swde", "chips": 4, "why": "test"}})
harness.load_benchmark = lambda: BENCH


def run(trace):
    return harness.run_cell({CELL!r}, 2**31 + 99, 0.0, trace,
                            t_start=time.perf_counter(), require_tpu=False,
                            conf=CONF, peaks=PEAKS, one_wave=True)
"""


def run_child(code: str, timeout: int = 600) -> dict:
    """Runs `code` after PRELUDE with four CPU devices; returns the JSON
    object it prints last."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c",
                          PRELUDE + textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env, cwd=ROOT)
    assert res.returncode == 0, res.stderr[-4000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_sound_mesh_run_is_correct():
    out = run_child("""
    print(json.dumps(run(True)))
    """)
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4 and out["failed"] == 0
    assert out["checks"]["unread_tokens"]["value"] == 0
    metrics = out["metrics"]
    assert metrics["extractions_per_query"]["value"] > 0
    # device-trace readers read nothing where the trace has no chip plane
    for name in ("collective_share", "prefill_roofline", "verify_roofline",
                 "model_roofline", "mfu", "device_idle_share"):
        assert name not in metrics


def test_exchange_left_out_is_not_correct():
    """Each chip keeps its own slice of a gathered activation and zeros for
    the other chips' slices: the all-gather left out."""
    out = run_child("""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    import repro.serving.engine as engine_module
    real = engine_module.make_constrain

    def make_constrain(mesh, batch_size, **kw):
        inner = real(mesh, batch_size, **kw)
        n = mesh.shape["model"]

        def own_slice(a):
            full = jnp.zeros(a.shape[:-1] + (a.shape[-1] * n,), a.dtype)
            return jax.lax.dynamic_update_slice_in_dim(
                full, a, jax.lax.axis_index("model") * a.shape[-1], -1)

        def constrain(x, kind):
            if kind == "gather" and x.shape[-1] % n == 0:
                spec = P(*([None] * (x.ndim - 1)), "model")
                x = jax.shard_map(own_slice, mesh=mesh, in_specs=spec,
                                  out_specs=P(), check_vma=False)(x)
            return inner(x, kind)
        return constrain
    engine_module.make_constrain = make_constrain
    print(json.dumps(run(False)))
    """)
    assert not out["correct"]
    checks = out["checks"]
    assert checks["logit_diff"]["value"] > checks["logit_diff"]["limit"]


def test_sharded_draw_equals_unsharded_draw():
    out = run_child("""
    import numpy as np
    from repro.distributed.sharding import param_shardings
    from repro.launch.mesh import _make_mesh
    ref = harness.load_reference(CONF)
    seed = 2**31 + 17
    mesh = _make_mesh((1, 4), ("data", "model"), devices=jax.devices())
    shardings = param_shardings(
        harness.model_config(CONF),
        jax.eval_shape(lambda: ref.make_weights(CONF, seed)), mesh,
        serving=True)
    whole = ref.make_weights(CONF, seed)
    split = ref.make_weights(CONF, seed, shardings)
    same = all(np.array_equal(np.asarray(a).view(np.uint16),
                              np.asarray(b).view(np.uint16))
               for a, b in zip(jax.tree.leaves(whole),
                               jax.tree.leaves(split)))
    laid_out = all(b.sharding == s for b, s in
                   zip(jax.tree.leaves(split), jax.tree.leaves(shardings)))
    embed = split["embed"].addressable_shards[0].data.shape
    print(json.dumps({"same": same, "laid_out": laid_out,
                      "embed_shard": list(embed)}))
    """)
    assert out["same"] and out["laid_out"]
    conf = json.loads((ROOT / "chipbench/testdata/smoke.json").read_text())
    assert out["embed_shard"] == [conf["vocab_size"] // 4,
                                  conf["hidden_size"]]


def test_serving_mesh_must_span_the_cells_chips():
    devices = [object()] * 4
    assert harness.serving_mesh({"slots": 8}, devices) is None
    with pytest.raises(SystemExit, match="needs 2 chips"):
        harness.serving_mesh({"mesh": [1, 2]}, devices)
