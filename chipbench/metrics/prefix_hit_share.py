"""Serving engine, prefix cache (`serving/prefix_cache.py`): share of the
traced wave's prompt tokens whose KV came from the prefix cache. Moves
queries_per_min."""


def read(r):
    saved, filled = r.engine["prefix_saved_tokens"], r.engine["prefill_tokens"]
    if not saved + filled:
        return None
    return 100.0 * saved / (saved + filled)
