"""Scheduler (`core/scheduler.py`): extractions per extraction round in the
traced wave, as a share of the engine's slots. Moves queries_per_min."""


def read(r):
    rounds = r.scheduler["rounds"]
    if not rounds:
        return None
    return 100.0 * r.scheduler["submitted"] / rounds / r.slots
