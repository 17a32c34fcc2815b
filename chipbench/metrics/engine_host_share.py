"""Serving engine (`serving/engine.py`): share of the traced wave in which
the engine's host work held the chip (the engine's `host_gap_s`: from each
read of a device value to the next phase-program dispatch, the device has
no phase program queued, so the Python, input copies and dispatch of that
stretch are time the chip waits for the host). Time the host spends
blocked on the device counts nothing here. Moves queries_per_min."""


def read(r):
    gap_s = r.engine.get("host_gap_s")
    if gap_s is None or not r.interval_s:
        return None
    return 100.0 * gap_s / r.interval_s
