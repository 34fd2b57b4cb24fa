"""Model FLOP utilization of the train window (see ``bench/readers.py``)."""
from bench.readers import mfu


def read(rec):
    return mfu(rec, "train")
