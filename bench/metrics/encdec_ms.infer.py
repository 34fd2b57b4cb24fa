"""Device ms per request under the ``enc`` and ``dec`` scopes (see
``bench/scopes.py``)."""
from bench import scopes


def read(rec):
    return scopes.device_ms(rec, "infer", scopes.ENCDEC_INFER)
