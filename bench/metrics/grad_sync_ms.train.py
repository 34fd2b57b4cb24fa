"""Device ms per training step under the ``grad_sync`` scope (the gradients'
mean over the chips), averaged over the chips (see ``bench/scopes.py``)."""
from bench import scopes


def read(rec):
    return scopes.device_ms(rec, "train", scopes.GRAD_SYNC)
