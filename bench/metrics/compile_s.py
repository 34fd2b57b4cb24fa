"""Host seconds JAX spent in set-up tracing, lowering, and compiling or
loading from the persistent cache the programs the window runs, from JAX's
compile-time events."""


def read(rec):
    return rec.compiles.seconds(0.0, rec.setup_end) or None
