"""Median ms of the engine's ``engine/gather`` span in the traced window:
a request's global field gathered into per-rank rows on the host (see
``bench/scopes.py``)."""
from bench import scopes


def read(rec):
    return scopes.span_ms(rec, "infer", "engine/gather")
