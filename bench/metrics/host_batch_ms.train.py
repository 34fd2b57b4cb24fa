"""Median host ms of the program's ``train/batch`` span in the traced
window: the step's Taylor-Green batch built on the host and placed (see
``bench/scopes.py``)."""
from bench import scopes


def read(rec):
    return scopes.span_ms(rec, "train", "train/batch")
