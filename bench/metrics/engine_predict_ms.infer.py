"""Median ms of the engine's ``engine/predict`` span in the traced window:
a batch's placement, device run and read-back (see ``bench/scopes.py``)."""
from bench import scopes


def read(rec):
    return scopes.span_ms(rec, "infer", "engine/predict")
