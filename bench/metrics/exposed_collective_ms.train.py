"""Device ms per training step in which a collective (all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all) runs on a chip
and no other op runs there, averaged over the chips (see
``bench/scopes.py`` and ``bench/trace.py``)."""
from bench import scopes


def read(rec):
    return scopes.exposed_collective_ms(rec, "train")
