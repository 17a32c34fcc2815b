"""Retrieval (`index/`): host milliseconds in the forked retriever's public
calls during the traced wave, per query. Moves queries_per_min."""


def read(r):
    return 1e3 * r.retrieval_s / r.k if r.whole else None
