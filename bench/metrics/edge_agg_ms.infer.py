"""Device ms per request under the NMP layers' ``edge_agg`` scopes (Eq.
4a-b; see ``bench/scopes.py``)."""
from bench import scopes


def read(rec):
    return scopes.device_ms(rec, "infer", scopes.EDGE_AGG)
