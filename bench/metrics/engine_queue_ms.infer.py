"""Median ms of the engine's ``engine/queue_wait`` span in the traced
window: a request's wait from ``submit`` to its dequeue (see
``bench/scopes.py``)."""
from bench import scopes


def read(rec):
    return scopes.span_ms(rec, "infer", "engine/queue_wait")
