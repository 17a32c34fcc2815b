"""Serving engine (`serving/engine.py`): prompt tokens prefilled plus tokens
served, per second of the traced wave. Moves queries_per_min."""


def read(r):
    served = sum(n_out for _, _, n_out, _ in r.requests)
    tokens = r.engine["prefill_tokens"] + served
    return tokens / r.interval_s if tokens else None
