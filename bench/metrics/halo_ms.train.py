"""Device ms per training step under the NMP layers' ``halo`` scopes (Eq.
4c-d: the halo exchange between chips and its synchronisation), forward and
backward, averaged over the chips (see ``bench/scopes.py``)."""
from bench import scopes


def read(rec):
    return scopes.device_ms(rec, "train", scopes.HALO)
