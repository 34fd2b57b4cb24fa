"""Median ms of the engine's ``engine/scatter`` span in the traced
window: a request's prediction scattered back to the global mesh on the
host (see ``bench/scopes.py``)."""
from bench import scopes


def read(rec):
    return scopes.span_ms(rec, "infer", "engine/scatter")
