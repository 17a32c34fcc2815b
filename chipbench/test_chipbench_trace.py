"""The trace reduction on a small trace recorded on one TPU v5e
(`testdata/small.xplane.pb`: four rounds of a 1024x1024 bf16 matmul program
marked `chipbench.engine.step` and a reduction program, each followed by a
3 ms host sleep marked `chipbench.retrieval.segments`, inside one
`chipbench.wave`), and the per-layer readers on hand-made readings."""
from pathlib import Path

import pytest

from chipbench import counts, devtrace, harness

TRACE = Path(__file__).parent / "testdata" / "small.xplane.pb"


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
