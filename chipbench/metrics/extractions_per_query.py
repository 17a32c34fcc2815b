"""Planner (`core/executor.py`, `core/ordering.py`): attribute extractions
the session ledger charged in the traced wave, per query. Moves
queries_per_min: fewer extractions, more queries in the window."""


def read(r):
    return r.ledger["extractions"] / r.k if r.whole else None
