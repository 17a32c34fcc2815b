"""Query traffic for the benchmark's mixes.

A mix file (`mixes/<name>.json`) names a corpus and the queries of one wave;
this module is the one generator that reads it. The query generators are
copies of the repository's benchmark generators (paper §5.1 single-table
queries from `benchmarks/common.py`, join queries from
`benchmarks/bench_join.py`), kept here so that no later change to those
files moves the benchmark's traffic.
"""
from __future__ import annotations

import json
import random
from pathlib import Path

from repro.core import Filter, JoinEdge, Query, conj, disj
from repro.core.expr import And, Or, evaluate_expr
from repro.data.corpus import CORPORA

MIX_DIR = Path(__file__).resolve().parent / "mixes"

JOINS = {
    ("players", "teams"): JoinEdge("players", "team_name", "teams", "team_name"),
    ("teams", "cities"): JoinEdge("teams", "location", "cities", "city_name"),
    ("teams", "owners"): JoinEdge("teams", "owner_name", "owners", "owner_name"),
}
NUMERIC = {
    "players": [("age", 25, 40), ("all_stars", 2, 12), ("ppg", 8.0, 25.0)],
    "teams": [("championships", 2, 15), ("founded", 1950, 1995),
              ("arena_capacity", 16000, 21000)],
    "cities": [("population", 100_000, 1_500_000), ("founded_year", 1800, 1900)],
    "owners": [("net_worth", 3.0, 30.0), ("owner_age", 45, 80)],
}


def load_mix(name: str) -> dict:
    path = MIX_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


# ------------------------------------------------- single-table (§5.1) ---


def _numeric_filter(rng, table, attr, values):
    vals = sorted(values)
    q = vals[max(0, min(len(vals) - 1, int(rng.uniform(0.15, 0.85) * len(vals))))]
    op = rng.choice([">", ">=", "<", "<=", "="])
    if op == "=" and len(set(vals)) > 20:      # equality on near-unique ints
        op = ">="
    return Filter(attr, op, q, table=table)


def _categorical_filter(rng, table, attr, values):
    return Filter(attr, "=", rng.choice(sorted(set(values))), table=table)


def generate_queries(corpus, table: str, n: int, *, seed: int = 0,
                     min_filters=1, max_filters=5) -> list:
    """Random single-table queries: conjunctions, disjunctions and mixed
    trees in roughly equal shares, kept only if some but not all rows
    qualify (paper §5.1)."""
    rng = random.Random(seed)
    truth = corpus.truth_rows(table)
    specs = corpus.attr_specs[table]
    attrs = sorted(specs)
    out = []
    guard = 0
    while len(out) < n and guard < n * 30:
        guard += 1
        k = rng.randint(min_filters, max_filters)
        chosen = rng.sample(attrs, min(k, len(attrs)))
        filters = []
        for a in chosen:
            vals = [t[a] for t in truth.values()]
            if specs[a].kind in ("int", "float"):
                filters.append(_numeric_filter(rng, table, a, vals))
            else:
                filters.append(_categorical_filter(rng, table, a, vals))
        mode = rng.choice(["and", "or", "mix"])
        if len(filters) == 1 or mode == "and":
            expr = conj(*filters)
        elif mode == "or":
            expr = disj(*filters)
        else:
            split = rng.randint(1, len(filters) - 1)
            left = conj(*filters[:split]) if split > 1 else filters[0]
            right = disj(*filters[split:]) if len(filters) - split > 1 else filters[split]
            expr = And((left, right)) if rng.random() < 0.5 else Or((left, right))
        sel_attr = rng.choice([a for a in attrs if specs[a].kind == "str"] or attrs)
        q = Query(tables=[table], select=[(table, sel_attr)], where=expr)
        n_true = sum(1 for t in truth.values() if evaluate_expr(expr, t))
        if 0 < n_true < len(truth):            # validated, non-degenerate
            out.append(q)
    return out


# -------------------------------------------------------------- joins ---


def _rand_filters(rng, table, k):
    out = []
    for attr, lo, hi in rng.sample(NUMERIC[table], min(k, len(NUMERIC[table]))):
        v = lo + (hi - lo) * rng.random()
        v = int(v) if isinstance(lo, int) else round(v, 1)
        out.append(Filter(attr, rng.choice([">", "<"]), v, table=table))
    return out


def make_join_queries(rng, n, *, tables=("players", "teams"), k_filters=(1, 2)):
    edge = JOINS[tables]
    out = []
    for _ in range(n):
        f1 = _rand_filters(rng, tables[0], rng.randint(*k_filters))
        f2 = _rand_filters(rng, tables[1], rng.randint(*k_filters))
        out.append(Query(tables=list(tables),
                         select=[(tables[0], NUMERIC[tables[0]][0][0])],
                         where=conj(*(f1 + f2)), joins=[edge]))
    return out


# ---------------------------------------------------------------- mix ---


def build_corpus(mix: dict):
    return CORPORA[mix["corpus"]](mix["corpus_seed"])


def wave_queries(mix: dict, corpus) -> list:
    """The K queries of one wave, fixed by the mix file alone: every seed
    and every wave does the same work."""
    out = []
    for spec in mix["queries"]:
        if spec["generator"] == "single_table":
            qs = generate_queries(corpus, spec["table"], spec["index"] + 1,
                                  seed=spec["seed"],
                                  min_filters=spec["min_filters"],
                                  max_filters=spec["max_filters"])
        elif spec["generator"] == "join":
            qs = make_join_queries(random.Random(spec["seed"]),
                                   spec["index"] + 1,
                                   tables=tuple(spec["tables"]),
                                   k_filters=tuple(spec["k_filters"]))
        else:
            raise ValueError(f"unknown query generator {spec['generator']!r}")
        out.append(qs[spec["index"]])
    return out


def submit_order(n: int, seed: int) -> list:
    """The order in which a wave submits its queries, drawn from the run's
    seed: seeds change the order, never the work."""
    order = list(range(n))
    random.Random(seed).shuffle(order)
    return order
