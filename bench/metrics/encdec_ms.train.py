"""Device ms per training step under the ``enc``, ``dec`` and ``loss``
scopes, forward and backward (see ``bench/scopes.py``)."""
from bench import scopes


def read(rec):
    return scopes.device_ms(rec, "train", scopes.ENCDEC_TRAIN)
