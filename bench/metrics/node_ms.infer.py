"""Device ms per request under the NMP layers' ``node`` scopes (Eq. 4e;
see ``bench/scopes.py``)."""
from bench import scopes


def read(rec):
    return scopes.device_ms(rec, "infer", scopes.NODE)
