"""The trace reduction on a small trace recorded on one TPU v5e
(`testdata/small.xplane.pb`: four rounds of a 1024x1024 bf16 matmul program
marked `chipbench.engine.step` and a reduction program, each followed by a
3 ms host sleep marked `chipbench.retrieval.segments`, inside one
`chipbench.wave`), and the per-layer readers on hand-made readings."""
from pathlib import Path

import pytest

from chipbench import counts, devtrace, harness, scopes

TRACE = Path(__file__).parent / "testdata" / "small.xplane.pb"
# three runs of a program named `prefill_chunk` on a (1, 4) mesh of a
# v5e-4 host, inside one `chipbench.wave`: two bf16 matmuls, each column
# sharded over the 4 chips and all-gathered (`%all-gather.N = ...`)
MESH_TRACE = Path(__file__).parent / "testdata" / "mesh.xplane.pb"


def test_reduce_recorded_trace():
    r = devtrace.reduce(devtrace.load(str(TRACE)), chips=1)
    assert 0 < r["busy_s"] < r["window_s"] < 0.05
    assert set(r["programs"]) == {"jit__lambda"}
    assert 0 < r["programs"]["jit__lambda"] < 1e-3
    names = dict(r["idle_gaps"])
    assert "chipbench.retrieval.segments" in names
    idle = sum(names.values())
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)


def test_reduce_without_device_planes():
    class Empty:
        planes = []
    assert devtrace.reduce(Empty(), chips=1)["busy_s"] == 0.0


class _Req:
    def __init__(self, plen, shared, out, accepted):
        self.prompt, self.shared_len = [0] * plen, shared
        self.out, self.accepted_tokens = [1] * out, accepted


def _readings(trace):
    conf = harness.load_config("qwen2.5-3b")
    wave = {"whole": True, "seconds": 10.0,
            "engine": {"prefill_tokens": 9000, "prefix_saved_tokens": 3000,
                       "prefill_chunks": 300, "decode_steps": 60},
            "scheduler": {"rounds": 4, "submitted": 100},
            "ledger": {"extractions": 100}, "retrieval_s": 0.5,
            "requests": [_Req(400, 120, 24, 2) for _ in range(100)]}
    return harness.Readings(conf, wave, 2, trace,
                            harness.load_peaks("TPU v5 lite"), 1)


@pytest.mark.parametrize("name,want", [
    ("extractions_per_query", 50.0), ("batch_fill", 100 * 25 / 32),
    ("retrieval_ms_per_query", 250.0), ("prefix_hit_share", 25.0),
    ("engine_tokens_per_s", (9000 + 2400) / 10.0)])
def test_counter_readers(name, want):
    assert harness.load_metric(name).read(_readings(None)) == \
        pytest.approx(want)


def test_trace_readers_stay_within_their_bounds():
    trace = {"busy_s": 6.0, "window_s": 10.0,
             "programs": {"jit_fn": 5.5, "jit_l2_rank_device": 0.5}}
    r = _readings(trace)
    assert harness.load_metric("device_idle_share").read(r) == \
        pytest.approx(40.0)
    roof = harness.load_metric("model_roofline").read(r)
    least = 360 * counts.weight_bytes_per_call(r.conf) / 819e9
    assert least / 5.5 * 100 <= roof <= 100.0
    assert 0 < harness.load_metric("mfu").read(r) < 100.0
    for name in ("mfu", "model_roofline", "device_idle_share"):
        assert harness.load_metric(name).read(_readings(None)) is None


@pytest.mark.parametrize("text,want", [
    ("%all-gather-start.3 = (bf16[2,8]{1,0}, bf16[8,8]{1,0}) "
     "all-gather-start(bf16[2,8]{1,0} %p.1), channel_id=1", True),
    ("%all-gather-done.3 = bf16[8,8]{1,0} all-gather-done((bf16[2,8]{1,0}, "
     "bf16[8,8]{1,0}) %all-gather-start.3)", True),
    ("%all-reduce.1 = f32[4]{0} all-reduce(f32[4]{0} %x), to_apply=%add",
     True),
    ("%reduce-scatter-fusion.2 = bf16[8]{0} fusion(bf16[32]{0} %x), "
     "kind=kCustom", True),
    ("%collective-permute-done = f32[4]{0} collective-permute-done(f32[4] "
     "%s)", True),
    ("%all-to-all.7 = f32[4,4]{1,0} all-to-all(f32[4,4]{1,0} %x)", True),
    ("%copy-start = (bf16[1024,1024]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) "
     "copy-start(bf16[1024,1024]{1,0:T(8,128)(2,1)} %x.1)", False),
    ("%fusion.12 = bf16[]{:T(256)} fusion(bf16[1024,1024]{1,0:T(8,128)"
     "(2,1)S(1)} %copy-done), kind=kOutput", False),
    ("%reduce.4 = f32[] reduce(f32[8] %all-gather.1, f32[] %c)", False),
])
def test_is_collective(text, want):
    assert devtrace.is_collective(text) is want


def test_collectives_of_a_one_chip_trace():
    r = devtrace.collectives(devtrace.load(str(TRACE)), chips=1)
    assert set(r) == {"jit__lambda"}
    device_s, collective_s = r["jit__lambda"]
    assert 0 < device_s < 1e-3 and collective_s == 0.0


def test_collective_share_reads_the_phase_programs():
    r = _readings(None)
    reader = harness.load_metric("collective_share")
    assert reader.read(r) is None
    r.collectives = {"jit_prefill_chunk": [4.0, 0.4],
                     "jit_verify_round": [1.0, 0.2],
                     "jit_put": [0.5, 0.5]}
    assert reader.read(r) == pytest.approx(100 * 0.6 / 5.0)
    r.collectives = {"jit_put": [0.5, 0.5]}
    assert reader.read(r) is None


US = 1_000_000                           # picoseconds


def _mesh_space(chips=2):
    """A hand-made XSpace: one marked wave on the host and, on each chip,
    two program executions whose ops are a while loop over a matmul fusion
    and an async all-gather (start, done), then a synchronous all-reduce;
    the second chip's collectives take twice as long."""
    space = scopes._xspace_class()()
    host = space.planes.add(name="/host:CPU")
    host.event_metadata.add(key=1).value.name = devtrace.WAVE_MARK
    line = host.lines.add(name="python3", timestamp_ns=0)
    line.events.add(metadata_id=1, offset_ps=0, duration_ps=100 * US)
    for c in range(chips):
        plane = space.planes.add(name=f"/device:TPU:{c}")
        names = {10: "jit_prefill_chunk(3)", 11: "jit_put(4)",
                 20: "%while.1 = (s32[]) while((s32[]) %t), body=%b",
                 21: "%fusion.2 = bf16[8,64]{1,0} fusion(bf16[8,16] %x), "
                     "kind=kOutput",
                 22: "%all-gather-start.1 = (bf16[8,16]{1,0}, "
                     "bf16[8,64]{1,0}) all-gather-start(bf16[8,16] %f)",
                 23: "%all-gather-done.1 = bf16[8,64]{1,0} "
                     "all-gather-done((bf16[8,16], bf16[8,64]) %s)",
                 24: "%all-reduce.3 = f32[8]{0} all-reduce(f32[8] %y)"}
        for key, name in names.items():
            plane.event_metadata.add(key=key).value.name = name
        mods = plane.lines.add(name="XLA Modules", timestamp_ns=0)
        ops = plane.lines.add(name="XLA Ops", timestamp_ns=0)
        k = c + 1                           # chip 1's collectives: 2 x
        for t0, mid in ((10, 10), (40, 11), (120, 10)):  # last: after
            mods.events.add(metadata_id=mid, offset_ps=t0 * US,
                            duration_ps=20 * US)
            ops.events.add(metadata_id=20, offset_ps=t0 * US,
                           duration_ps=12 * US)
            ops.events.add(metadata_id=21, offset_ps=(t0 + 1) * US,
                           duration_ps=4 * US)
            ops.events.add(metadata_id=22, offset_ps=(t0 + 5) * US,
                           duration_ps=1 * k * US // 2)
            ops.events.add(metadata_id=21, offset_ps=(t0 + 6) * US,
                           duration_ps=3 * US)
            ops.events.add(metadata_id=23, offset_ps=(t0 + 9) * US,
                           duration_ps=2 * k * US)
            ops.events.add(metadata_id=24, offset_ps=(t0 + 14) * US,
                           duration_ps=k * US)
    from jax._src.profiler import ProfileData
    return ProfileData.from_serialized_xspace(space.SerializeToString())


def test_collectives_of_a_mesh_trace():
    """Per chip and program: device seconds of the executions that start
    in the wave, and seconds of the leaf collective ops (the while loop
    that spans its body is no leaf), averaged over the chips."""
    r = devtrace.collectives(_mesh_space(), chips=2)
    assert set(r) == {"jit_prefill_chunk", "jit_put"}
    pre_s, pre_c = r["jit_prefill_chunk"]
    assert pre_s == pytest.approx(20e-6)
    # chip 0: 0.5 + 2 + 1 us; chip 1: 1 + 4 + 2 us
    assert pre_c == pytest.approx((3.5e-6 + 7e-6) / 2)
    assert r["jit_put"][1] == pytest.approx(pre_c)
    assert devtrace.collectives(_mesh_space(), chips=1)[
        "jit_prefill_chunk"][1] == pytest.approx(3.5e-6)
    r = _readings(None)
    r.collectives = devtrace.collectives(_mesh_space(), chips=2)
    assert harness.load_metric("collective_share").read(r) == \
        pytest.approx(100 * 5.25 / 20)


def test_collectives_of_a_recorded_mesh_trace():
    pd = devtrace.load(str(MESH_TRACE))
    r = devtrace.collectives(pd, chips=4)
    assert set(r) == {"jit_prefill_chunk"}
    device_s, collective_s = r["jit_prefill_chunk"]
    assert 0 < collective_s < device_s < 1e-3
    # the all-gathers are most of these small programs' time
    assert collective_s / device_s > 0.1
    assert devtrace.reduce(pd, chips=4)["busy_s"] == \
        pytest.approx(device_s, rel=1e-6)
