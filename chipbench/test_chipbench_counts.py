"""The benchmark's FLOP and byte counts and its weight layout against the
program's own parameter tree, `jax.eval_shape(init_params)`, for both
configurations at full width (shapes only, nothing is allocated)."""
import jax
import pytest

from chipbench import counts, harness

CONFIGS = ["qwen2.5-3b", "qwen3-32b-8L", "qwen3-32b-32L-tp4"]


def _program_tree(conf):
    from repro.models import init_params
    return jax.eval_shape(init_params, harness.model_config(conf),
                          jax.random.PRNGKey(0))


@pytest.mark.parametrize("name", CONFIGS)
def test_param_count_matches_program(name):
    conf = harness.load_config(name)
    tree = _program_tree(conf)
    assert counts.total_params(conf) == sum(a.size for a in
                                            jax.tree.leaves(tree))


@pytest.mark.parametrize("name", CONFIGS)
def test_weight_layout_matches_program(name):
    conf = harness.load_config(name)
    reference = harness.load_reference(conf)
    got = jax.eval_shape(lambda k: reference._draw(conf, k),
                         jax.random.key(0))
    want = _program_tree(conf)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_published_sizes():
    """The sizes the configuration files stand for (PERF.md, section 4)."""
    small = harness.load_config("qwen2.5-3b")
    big = harness.load_config("qwen3-32b-8L")
    assert round(counts.total_params(small) / 1e9, 2) == 3.09
    assert round(counts.total_params(big) / 1e9, 2) == 5.46
    assert counts.kv_bytes_per_token(small) == 36_864
    assert counts.kv_bytes_per_token(big) == 32_768


def test_published_sizes_of_the_tp4_stage():
    """32 layers of Qwen3-32B with the embedding and the head: 17.16 B
    parameters, 8.58 GB a chip of four; its KV 131,072 B a token."""
    conf = harness.load_config("qwen3-32b-32L-tp4")
    n = counts.total_params(conf)
    assert round(n / 1e9, 2) == 17.16
    assert round(2 * n / 4 / 1e9, 2) == 8.58
    assert counts.kv_bytes_per_token(conf) == 131_072


def test_useful_work_floors():
    conf = harness.load_config("qwen2.5-3b")
    trunk = counts.trunk_matmul_params(conf)
    head = counts.head_params(conf)
    pair = counts.attn_flops_per_pair(conf)
    # one request: 10-token prompt with a 4-token cached prefix, 3 served
    # tokens, one draft accepted
    w = counts.useful_work(conf, [(10, 4, 3, 1)], prefill_tokens=6)
    assert w["prefill_flops"] == 2 * trunk * 6 + 2 * head + pair * (55 - 10)
    assert w["decode_flops"] == 2 * (trunk + head) * 2 + pair * (11 + 12)
    assert w["prefill_bytes"] == 6 * counts.kv_bytes_per_token(conf)
    assert w["decode_bytes"] == 1 * 10 * counts.kv_bytes_per_token(conf)
    assert w["served_tokens"] == 3
