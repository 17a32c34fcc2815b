"""`scopes.py`, the per-scope device-time tool, on a hand-made XSpace: leaf
ops are put down to their program and to the named scope in their op
path, unscoped ones to their HLO category, and loop ops that span their
body's ops count nothing themselves. No chip needed."""
from pathlib import Path

import pytest

from chipbench import scopes

ROOT = Path(__file__).resolve().parents[1]


def _space(device_plane="/device:TPU:0"):
    space = scopes._xspace_class()()
    plane = space.planes.add(name=device_plane)
    for key, name in [(1, "tf_op"), (2, "bytes_accessed"),
                      (3, "hlo_category")]:
        e = plane.stat_metadata.add(key=key)
        e.value.id, e.value.name = key, name

    def op(key, name, tf_op=None, nbytes=0, cat=None):
        e = plane.event_metadata.add(key=key)
        e.value.id, e.value.name = key, name
        if tf_op is not None:
            e.value.stats.add(metadata_id=1, str_value=tf_op)
        if nbytes:
            e.value.stats.add(metadata_id=2, int64_value=nbytes)
        if cat is not None:
            e.value.stats.add(metadata_id=3, str_value=cat)
    op(10, "jit_prefill_chunk(17)")
    op(11, "jit_verify_round(18)")
    op(20, "while.1", "jit(prefill_chunk)/while")
    op(21, "fusion.3", "jit(prefill_chunk)/while/body/mlp/dot_general:",
       nbytes=4000)
    op(22, "copy.7", "jit(prefill_chunk)/while/body/copy", nbytes=800,
       cat="data formatting")
    op(23, "fusion.9", "jit(verify_round)/kv_gather/gather", nbytes=60)
    us = 1_000_000                          # picoseconds
    mods = plane.lines.add(name="XLA Modules", timestamp_ns=1000)
    mods.events.add(metadata_id=10, offset_ps=0, duration_ps=10 * us)
    mods.events.add(metadata_id=11, offset_ps=20 * us, duration_ps=5 * us)
    ops = plane.lines.add(name="XLA Ops", timestamp_ns=1000)
    ops.events.add(metadata_id=20, offset_ps=0, duration_ps=10 * us)
    ops.events.add(metadata_id=21, offset_ps=1 * us, duration_ps=3 * us)
    ops.events.add(metadata_id=22, offset_ps=5 * us, duration_ps=4 * us)
    ops.events.add(metadata_id=21, offset_ps=9 * us, duration_ps=1 * us)
    ops.events.add(metadata_id=23, offset_ps=21 * us, duration_ps=2 * us)
    return space.SerializeToString()


def test_scope_table_puts_leaf_ops_down_to_program_and_scope():
    t = scopes.scope_table(_space())
    assert set(t) == {"jit_prefill_chunk", "jit_verify_round"}
    pre = t["jit_prefill_chunk"]
    assert set(pre) == {"mlp", "other:data formatting"}
    assert pre["mlp"][0] == pytest.approx(4e-6)
    assert pre["mlp"][1] == 8000
    assert pre["other:data formatting"] == [pytest.approx(4e-6), 800]
    assert t["jit_verify_round"] == {"kv_gather": [pytest.approx(2e-6), 60]}


def test_scope_table_is_empty_without_a_chip_plane():
    assert scopes.scope_table(_space("/host:CPU")) == {}


def test_scope_table_reads_a_chip_trace():
    # four rounds of an unscoped bf16 matmul program on a TPU v5e
    data = (ROOT / "chipbench" / "testdata" / "small.xplane.pb").read_bytes()
    t = scopes.scope_table(data)
    assert set(t) == {"jit__lambda"}
    ops = t["jit__lambda"]
    assert all(k.startswith("other:") for k in ops)
    assert "other:convolution fusion" in ops
    assert all(s > 0 and b > 0 for s, b in ops.values())
