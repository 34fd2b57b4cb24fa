"""Device ms per training step under the NMP layers' ``node`` scopes
(Eq. 4e), forward and backward (see ``bench/scopes.py``)."""
from bench import scopes


def read(rec):
    return scopes.device_ms(rec, "train", scopes.NODE)
