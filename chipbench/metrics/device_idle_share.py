"""Device: share of the traced wave in which no program ran on the chip,
from the profiler's trace (`devtrace.py`). Moves queries_per_min."""


def read(r):
    t = r.trace
    if not t or not t["window_s"] or not t["busy_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
