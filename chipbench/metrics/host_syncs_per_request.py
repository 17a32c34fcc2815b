"""Serving engine (`serving/engine.py`): host reads that blocked on a device
value in the traced wave's engine steps (the engine's `host_syncs` counter:
one per insert's first token, two per verify round), per request the wave
finished. Each leaves the chip idle until the next program is dispatched.
Moves queries_per_min."""


def read(r):
    syncs = r.engine.get("host_syncs")
    if syncs is None or not r.requests:
        return None
    return syncs / len(r.requests)
