"""Chip benchmark of the QUEST served analytics path (see PERF.md).

`run.py` is the entry point; BENCHMARK.json at the repository root names the
cells, each a configuration file under `configs/` and a traffic mix under
`mixes/`, and each per-layer metric a reader under `metrics/`.
"""
