"""Serving engine (`serving/engine.py`): mean milliseconds from a request's
submission to the engine until its first token reached the host, over the
traced wave's requests (the engine's `ttft_s` over `first_tokens`). Moves
queries_per_min."""


def read(r):
    total, n = r.engine.get("ttft_s"), r.engine.get("first_tokens")
    if total is None or not n:
        return None
    return 1e3 * total / n
