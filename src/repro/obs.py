"""Tracing inside the program: host spans and the compiled text of the
jitted entry points.

* :func:`span` times a block of host work. Under ``jax.profiler`` it lands
  in the trace as ``repro:<name>``, on the same clock as the device's ops;
  always, it appends ``(name, t0, t1, id)`` on ``time.perf_counter`` to a
  bounded process-wide ring, which :func:`spans` reads back. ``id`` ties the
  spans of one training step or one request together. :func:`record` adds
  a span whose start was taken earlier, such as a queue wait.
* :func:`program` wraps a jitted entry point. A call that compiles keeps the
  arguments' shapes, dtypes and shardings (never the arrays), so that
  :func:`hlo` can later give the compiled HLO of the program that ran. Each
  top-level instruction's ``op_name`` there carries the ``jax.named_scope``
  of the model layer it belongs to; the profiler's op events carry the
  instruction's name.

Recording is always on: one span costs about a microsecond on the host when
no profiler runs.

Scopes on the device (``jax.named_scope``): ``enc``, ``nmp{i}`` with
``edge_agg`` (Eq. 4a-b; its table-driven sums, where the graph carries
slot tables, under ``edge_agg/slot_sum``), ``halo`` (Eq. 4c-d) and ``node``
(Eq. 4e) inside, ``vcycle/l{k}``, ``dec``, ``loss``, ``grad_sync``,
``adamw``. Host spans:
``train/batch``, ``engine/queue_wait``, ``engine/gather``,
``engine/predict``, ``engine/scatter``. Programs: ``grad_step``,
``update``, ``rollout_predict``.
"""
from __future__ import annotations

import collections
import time

import jax

PREFIX = "repro:"
RING_SIZE = 65536
_annotation = jax.profiler.TraceAnnotation

# deque.append is atomic, so threads append without a lock
_ring: collections.deque = collections.deque(maxlen=RING_SIZE)
_programs: dict[str, tuple] = {}


class span:
    """Context manager timing a block of host work as span ``name``."""

    __slots__ = ("name", "id", "_t0", "_ann")

    def __init__(self, name: str, id=None):
        self.name, self.id = name, id

    def __enter__(self):
        # the annotation is made only while a profiler records
        self._ann = _annotation(PREFIX + self.name) if _annotation.is_enabled() else None
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _ring.append((self.name, self._t0, t1, self.id))
        return False


def record(name: str, t0: float, t1: float, id=None):
    """Record span ``name`` from ``t0`` to ``t1`` (``time.perf_counter``)."""
    _ring.append((name, t0, t1, id))


def spans(t0: float = float("-inf"), t1: float = float("inf")) -> list[tuple]:
    """The recorded ``(name, start, end, id)`` that lie inside ``[t0, t1]``,
    oldest first."""
    while True:
        try:
            held = list(_ring)
            break
        except RuntimeError:        # another thread appended while copying
            continue
    return [s for s in held if t0 <= s[1] and s[2] <= t1]


def _abstract(leaf):
    if not hasattr(leaf, "shape"):
        return leaf
    return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                sharding=getattr(leaf, "sharding", None),
                                weak_type=getattr(leaf, "weak_type", False))


class _Program:
    """A jitted entry point that registers its arguments' shapes each time a
    call compiles a new variant of it (so the last one registered is the one
    that ran since); every attribute (``lower``, ...) is the jitted
    function's."""

    def __init__(self, name: str, jitted):
        self._name, self._jitted, self._variants = name, jitted, 0

    def __call__(self, *args):
        out = self._jitted(*args)
        variants = self._jitted._cache_size()     # jit's count of compiled variants
        if variants != self._variants:
            self._variants = variants
            _programs[self._name] = (self._jitted, jax.tree.map(_abstract, args))
        return out

    def __getattr__(self, attr):
        return getattr(self._jitted, attr)


def program(name: str, jitted) -> _Program:
    """Wrap ``jitted`` as the program ``name``: :func:`hlo` compiles the
    variant that a call of such a wrapper compiled last."""
    return _Program(name, jitted)


def programs() -> list[str]:
    """The names of the programs that have run."""
    return sorted(_programs)


def hlo(name: str) -> str:
    """The compiled HLO text of program ``name`` at the shapes it last
    compiled for. It lowers and compiles again, which in the process that
    ran the program hits JAX's compilation caches: call it after the
    measured work, never inside it."""
    jitted, args = _programs[name]
    return jitted.lower(*args).compile().as_text()
