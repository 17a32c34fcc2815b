"""The readers of the engine's phase programs and host counters on hand-made
readings: each reads its program or counters, and returns None where the
program never ran or the program under test has no such counter (a checkout
whose engine predates them)."""
import pytest

from chipbench import counts, harness

ENGINE = {"prefill_tokens": 9000, "prefix_saved_tokens": 3000,
          "prefill_chunks": 300, "decode_steps": 60, "host_syncs": 220,
          "sync_wait_s": 1.5, "step_s": 4.0, "host_gap_s": 2.5,
          "first_tokens": 100,
          "ttft_s": 250.0}
PROGRAMS = {"jit_prefill_chunk": 4.0, "jit_verify_round": 1.5,
            "jit_l2_rank_device": 0.5}


class _Req:
    def __init__(self, plen, shared, out, accepted):
        self.prompt, self.shared_len = [0] * plen, shared
        self.out, self.accepted_tokens = [1] * out, accepted


def _readings(trace, engine=ENGINE):
    conf = harness.load_config("qwen2.5-3b")
    wave = {"whole": True, "seconds": 10.0, "engine": dict(engine),
            "scheduler": {"rounds": 4, "submitted": 100},
            "ledger": {"extractions": 100}, "retrieval_s": 0.5,
            "requests": [_Req(400, 120, 24, 2) for _ in range(100)]}
    return harness.Readings(conf, wave, 2, trace,
                            harness.load_peaks("TPU v5 lite"), 1)


def _trace(programs=PROGRAMS):
    return {"busy_s": 6.0, "window_s": 10.0, "programs": dict(programs)}


@pytest.mark.parametrize("name,want", [
    ("host_syncs_per_request", 2.2), ("engine_host_share", 25.0),
    ("ttft_ms", 2500.0)])
def test_counter_readers(name, want):
    assert harness.load_metric(name).read(_readings(None)) == \
        pytest.approx(want)


@pytest.mark.parametrize("name", ["host_syncs_per_request",
                                  "engine_host_share", "ttft_ms"])
def test_counter_readers_without_the_counters(name):
    old = {k: v for k, v in ENGINE.items()
           if k not in ("host_syncs", "sync_wait_s", "step_s",
                        "host_gap_s", "first_tokens", "ttft_s")}
    assert harness.load_metric(name).read(_readings(None, old)) is None


def test_phase_rooflines_split_model_roofline():
    r = _readings(_trace())
    pre = harness.load_metric("prefill_roofline").read(r)
    ver = harness.load_metric("verify_roofline").read(r)
    whole = harness.load_metric("model_roofline").read(r)
    per_call = counts.weight_bytes_per_call(r.conf) / 819e9
    assert 300 * per_call / 4.0 * 100 <= pre <= 100.0
    assert 60 * per_call / 1.5 * 100 <= ver <= 100.0
    # the two programs' least times over their own device time: the share
    # of the whole wave's programs, less the other programs' time
    least = pre / 100 * 4.0 + ver / 100 * 1.5
    assert least / 5.5 * 100 == pytest.approx(whole)


@pytest.mark.parametrize("name,program", [
    ("prefill_roofline", "jit_prefill_chunk"),
    ("verify_roofline", "jit_verify_round")])
def test_phase_rooflines_without_their_program(name, program):
    reader = harness.load_metric(name)
    assert reader.read(_readings(None)) is None
    others = {k: v for k, v in PROGRAMS.items() if k != program}
    assert reader.read(_readings(_trace(others))) is None
    # the parent engine's programs were one `jit_fn`
    assert reader.read(_readings(_trace({"jit_fn": 5.5}))) is None
