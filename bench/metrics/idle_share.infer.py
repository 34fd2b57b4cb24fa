"""Device idle share of the traced infer window (see ``bench/readers.py``)."""
from bench.readers import idle_share


def read(rec):
    return idle_share(rec, "infer")
