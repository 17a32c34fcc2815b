"""BENCHMARK.json and the files it names: every configuration, mix and
per-layer metric is a file of its own, found by name, and every name and
unit keeps to the benchmark's character sets. No chip needed."""
import json
import re
from pathlib import Path

import pytest

from chipbench import harness, traffic

ROOT = Path(__file__).resolve().parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
# keys that name a width, which `reduced` may never list
WIDTH = re.compile(r"hidden_size|intermediate|head_dim|num_attention_heads|"
                   r"num_key_value_heads|latent|state|proj|_dim$|_rank$|"
                   r"expan|per_tok")


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    n = len(BENCH["workloads"])
    # a full check of 24 cells must fit its 43200 s
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, n // 2)


@pytest.mark.parametrize("group", ["configs", "workloads", "end_to_end",
                                   "per_layer"])
def test_names_and_units(group):
    names = [e["name"] for e in BENCH[group]]
    assert len(names) == len(set(names))
    for e in BENCH[group]:
        assert NAME.fullmatch(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.fullmatch(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key]
                assert "\t" not in e[key]


def test_configs_found_by_name():
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        conf = harness.load_config(c["name"])
        assert conf["name"] == c["name"] and conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        assert not any(WIDTH.search(k) for k in c["reduced"]), c["reduced"]
        assert {"deployment", "assumed", "serving"} <= set(conf)
        reference = harness.load_reference(conf)
        assert callable(reference.make_weights)
        assert callable(reference.compare_requests)
        assert callable(harness.load_module(conf["counts"]).useful_work)
        assert harness.model_config(conf).family == conf["program"]["family"]
        assert set(conf["limits"]) == {"logit_gap", "logit_diff"}
    with pytest.raises(FileNotFoundError):
        harness.load_reference({"reference": "no_such_reference"})


def test_mixes_found_by_name():
    for w in BENCH["workloads"]:
        mix = traffic.load_mix(w["traffic"])
        assert mix["name"] == w["traffic"] and mix["queries"]
        assert w["name"].startswith(f"{w['config']}.")
    with pytest.raises(FileNotFoundError):
        traffic.load_mix("no-such-mix")


def test_metrics_found_by_name():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert callable(harness.load_metric(m["name"]).read)
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        layers.setdefault(m["layer"], []).append(m["name"])
    assert any("mfu" in m["name"] for m in BENCH["per_layer"])
    with pytest.raises(FileNotFoundError):
        harness.load_metric("no_such_metric")


def test_peaks_known_device_only():
    assert harness.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.load_peaks("cpu")


def test_serving_mesh_spans_each_cells_chips():
    """A configuration's serving mesh holds as many chips as every cell
    that runs it asks for (one chip where it has no mesh), and at most
    half of the cells, rounded down (one always may), ask for four."""
    for w in BENCH["workloads"]:
        mesh = harness.load_config(w["config"])["serving"].get("mesh", [1])
        assert len(mesh) in (1, 2) and all(n >= 1 for n in mesh)
        n = 1
        for k in mesh:
            n *= k
        assert n == w["chips"], (w["name"], mesh)
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)
