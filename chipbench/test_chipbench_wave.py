"""Runs of the harness off the chip, on a test-sized model
(`testdata/smoke.json`): one wave of each mix gives the oracle's rows; a
whole run with the timed path sound comes out correct, and with a served
token or an extracted answer altered where it is produced comes out not
correct; the reference put in the program's place in int8 or fp8 (the
control) comes out not correct, with wider readings than the program.

At this size (CPU, seeds 2**31+3, 2**31+99 and 7) the program's served
tokens lie at most 0.0003 below the float32 reference's best and its logits
at most 0.0032 from the reference's; the int8 control reads logit distances
of 0.0053 to 0.0060, the fp8 control 0.023 and gaps of 0.006 to 0.013. So
the test configuration's limits are 0.001 and 0.004; the cells' own limits,
in their configuration files, come from chip readings (PERF.md)."""
import json
import time
from pathlib import Path

import jax
import pytest

from chipbench import control, harness, traffic

CONF = json.loads((Path(__file__).parent / "testdata" / "smoke.json")
                  .read_text())
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SMOKE_LIMITS = CONF["limits"]
CELLS = {"swde": "qwen2.5-3b.swde", "wiki-join": "qwen2.5-3b.wiki-join"}


@pytest.fixture(autouse=True)
def _jax_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_compilation_cache_max_size")
    was = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in was.items():
        jax.config.update(k, v)


@pytest.mark.parametrize("mix_name", sorted(CELLS))
def test_one_wave_gives_oracle_rows(mix_name):
    mix = traffic.load_mix(mix_name)
    ctx = harness.build(CONF, mix, seed=2**31 + 5)
    want = harness.oracle_rows(ctx["corpus"], ctx["retriever"],
                               ctx["queries"], ctx["serving"])
    book = []
    wave = harness.run_wave(ctx, book=book)
    assert wave["whole"] and len(wave["query_s"]) == len(ctx["queries"])
    assert [harness._canon(r) for r in wave["rows"]] == want
    assert any(want)
    errors, checked = harness.ranking_errors(book)
    assert errors == 0 and checked > 0
    assert wave["ledger"]["extractions"] > 0 and wave["tokens"] > 0


def _run(seconds=0.0, one_wave=True):
    return harness.run_cell(CELLS["swde"], 2**31 + 99, seconds, False,
                            t_start=time.perf_counter(), require_tpu=False,
                            conf=CONF, peaks=PEAKS, one_wave=one_wave)


def test_sound_run_is_correct():
    # a window long enough for a whole wave on a loaded CPU (7-15 s)
    out = _run(seconds=40.0, one_wave=False)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"query_s", "queries_per_min", "setup_s"}
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert out["checks"]["unread_tokens"]["value"] == 0
    assert list(out)[-1] == "checks"


def test_altered_token_is_not_correct(monkeypatch):
    from repro.serving.engine import ServingEngine
    real = ServingEngine._spec_step

    def altered(self):
        real(self)
        for req in self.active.values():    # one token per round, changed
            req.out[-1] = (req.out[-1] + 1) % CONF["vocab_size"]
            break
    monkeypatch.setattr(ServingEngine, "_spec_step", altered)
    out = _run()
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > SMOKE_LIMITS["logit_gap"]


def test_altered_answer_is_not_correct(monkeypatch):
    from repro.extract.served import ServedExtractor
    real = ServedExtractor._parse
    calls = []

    def altered(self, doc_id, attr, answer, context):
        calls.append(1)
        value = real(self, doc_id, attr, answer, context)
        return None if len(calls) % 2 else value
    monkeypatch.setattr(ServedExtractor, "_parse", altered)
    out = _run()
    assert not out["correct"]
    assert out["checks"]["rows_mismatched_queries"]["value"] > 0


def test_control_reads_wider_gaps_than_the_program():
    r, = control.readings(CELLS["swde"], [2**31 + 3], require_tpu=False,
                          conf=CONF)
    assert r["correct"], r["checks"]
    for q in control.CONTROLS:
        c = r["controls"][q]
        assert not c["correct"], (q, c["checks"])
        assert c["checks"]["logit_diff"] > SMOKE_LIMITS["logit_diff"] \
            > r["checks"]["logit_diff"]
        assert r["logit_rms"][q] > r["logit_rms"]["served"]
    assert r["controls"]["fp8"]["checks"]["logit_gap"] > \
        SMOKE_LIMITS["logit_gap"] >= r["checks"]["logit_gap"]
