"""Device ms per training step under the NMP layers' ``edge_agg`` scopes
(Eq. 4a-b: gathers, edge MLP, aggregate), forward and backward (see
``bench/scopes.py``)."""
from bench import scopes


def read(rec):
    return scopes.device_ms(rec, "train", scopes.EDGE_AGG)
