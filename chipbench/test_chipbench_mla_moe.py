"""DeepSeek-V2-Lite's EP4 share in the benchmark, off the chip: the counts
and the weight layout against the program's parameter tree (shapes only,
nothing is allocated), the sizes the configuration stands for, a run of the
harness on a test-sized MLA+MoE model (`testdata/mla_moe_smoke.json`)
against `mla_moe_reference` and its int8 and fp8 controls, and the expert
layer's readers.

At the test size (float32, CPU, seeds 2**31+99 and 7) the served logits lie
at most 2.4e-7 from the reference's and the served tokens are its best; the
int8 control reads logit distances of 0.0063, fp8 0.025. So the test
configuration's limits are 1e-3 for both numbers; the cell's own limits, in
its configuration file, come from chip readings (PERF.md)."""
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from chipbench import control, harness, mla_moe_counts as counts

NAME = "deepseek-v2-lite-16b-ep4"
SMOKE = json.loads((Path(__file__).parent / "testdata" / "mla_moe_smoke.json")
                   .read_text())
CONFS = {NAME: harness.load_config(NAME), "smoke": SMOKE}


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


def _program_tree(conf):
    from repro.models import init_params
    return jax.eval_shape(init_params, harness.model_config(conf),
                          jax.random.PRNGKey(0))


@pytest.mark.parametrize("name", sorted(CONFS))
def test_weights_match_program_and_counts(name):
    conf = CONFS[name]
    reference = harness.load_reference(conf)
    got = jax.eval_shape(lambda: reference.make_weights(conf, 2**31 + 1))
    want = _program_tree(conf)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
    assert counts.total_params(conf) == sum(a.size for a in
                                            jax.tree.leaves(got))


def test_published_sizes_of_the_ep4_share():
    """One chip's share (PERF.md, section 4): 169.6 M parameters an expert
    layer, 81.0 M the dense layer, 419.4 M the embedding and head, 4.91 B
    (9.82 GB) in all; the whole model 15.71 B; the latent cache 31,104 B a
    token; the held experts 7.20 GB, 77% of the 9.40 GB a call reads (the
    embedding is gathered, not read whole) and 73% of the 9.82 GB held."""
    conf = CONFS[NAME]
    per = counts.attn_params(conf) + counts.norm_params(conf)
    assert round((per + counts.moe_params(conf)) / 1e6, 1) == 169.6
    assert round((per + counts.dense_mlp_params(conf)) / 1e6, 1) == 81.0
    assert round(2 * counts.head_params(conf) / 1e6, 1) == 419.4
    n = counts.total_params(conf)
    assert round(n / 1e9, 2) == 4.91 and round(2 * n / 1e9, 2) == 9.82
    whole = dict(conf, n_routed_experts=conf["published_n_routed_experts"])
    assert round(counts.total_params(whole) / 1e9, 2) == 15.71
    assert counts.kv_bytes_per_token(conf) == 27 * 576 * 2 == 31_104
    held = 26 * conf["n_routed_experts"] * counts.expert_params(conf) * 2
    assert round(held / counts.weight_bytes_per_call(conf), 2) == 0.77
    assert round(held / 1e9, 2) == 7.20


def test_useful_work_floors():
    conf = CONFS[NAME]
    trunk = counts.token_matmul_params(conf)
    head = counts.head_params(conf)
    pair = counts.attn_flops_per_pair(conf)
    assert pair == 27 * 2 * 16 * (2 * 512 + 64)
    # the routed experts at their expectation: 6 picks, a quarter held here
    assert trunk - (counts.token_matmul_params(dict(conf, num_experts_per_tok=0))) \
        == 26 * 6 * 16 / 64 * counts.expert_params(conf)
    w = counts.useful_work(conf, [(10, 4, 3, 1)], prefill_tokens=6)
    assert w["prefill_flops"] == 2 * trunk * 6 + 2 * head + pair * (55 - 10)
    assert w["decode_flops"] == 2 * (trunk + head) * 2 + pair * (11 + 12)
    assert w["prefill_bytes"] == 6 * counts.kv_bytes_per_token(conf)
    assert w["decode_bytes"] == 1 * 10 * counts.kv_bytes_per_token(conf)


def test_run_agrees_with_the_reference_and_the_control_does_not():
    """Engine prefill, then verify rounds through the paged cache, against
    the float32 reference over the served tokens of a whole wave."""
    r, = control.readings("qwen2.5-3b.swde", [2**31 + 99],
                          require_tpu=False, conf=SMOKE)
    assert r["correct"], r["checks"]
    assert r["checks"]["unread_tokens"] == 0
    for q in control.CONTROLS:
        c = r["controls"][q]
        assert not c["correct"], (q, c["checks"])
        assert c["checks"]["logit_diff"] > SMOKE["limits"]["logit_diff"] \
            > r["checks"]["logit_diff"]


def test_reference_counts_routing_flips_in_bfloat16():
    """With a control asked for, the bfloat16 shadow routes some (position,
    layer) pairs differently (9 of 2,048 here); in float32 it is the
    reference itself and routes none differently. Without controls, as in
    a timed run, the shadow pass is not made."""
    from chipbench import mla_moe_reference as ref
    toks = jax.random.randint(jax.random.PRNGKey(0), (4, 256), 0, 512)
    seqs = [(list(map(int, t[:250])), list(map(int, t[250:])),
             jax.numpy.zeros((6, 512))) for t in toks]
    for dtype in ("bfloat16", "float32"):
        conf = dict(SMOKE, torch_dtype=dtype)
        w = ref.make_weights(conf, 3)
        assert "route_flips" not in ref.compare_requests(
            conf, w, seqs, seq_len=256, batch=2)
        out = ref.compare_requests(conf, w, seqs, seq_len=256, batch=2,
                                   quants=("int8",))
        flips, pairs = out["route_flips"]
        assert pairs == 4 * 256 * 2
        assert (0 < flips < pairs // 100) if dtype == "bfloat16" else \
            flips == 0


def _readings(engine: dict, conf=CONFS[NAME]):
    return SimpleNamespace(engine=engine, conf=conf)


def test_expert_layer_readers():
    held_tokens = harness.load_metric("held_expert_tokens")
    share = harness.load_metric("held_assignment_share")
    # 40 calls, each 32 tokens x 26 layers x 6 picks, a quarter held
    e = {"prefill_chunks": 30, "decode_steps": 10,
         "held_assignments": 40 * 32 * 26 * 6 // 4,
         "routed_assignments": 40 * 32 * 26 * 6}
    assert held_tokens.read(_readings(e)) == pytest.approx(3.0)
    assert share.read(_readings(e)) == pytest.approx(25.0)
    # the parent's engine has no such counters: nothing to read
    parent = {"prefill_chunks": 30, "decode_steps": 10}
    assert held_tokens.read(_readings(parent)) is None
    assert share.read(_readings(parent)) is None
    dense = harness.load_config("qwen2.5-3b")
    assert held_tokens.read(_readings(dict(e, routed_assignments=0,
                                           held_assignments=0), dense)) is None

