"""Engine host path per request: the client's latency less the device busy
time inside it, the median over the traced window's requests."""
from bench.readers import host_ms


def read(rec):
    return host_ms(rec, "infer", "request")
