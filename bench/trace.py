"""The profiler's trace of a window, reduced to what the metrics read.

``capture`` runs the window under ``jax.profiler`` and turns the
benchmark's host spans into trace annotations; ``reduce`` reads the
``.xplane.pb`` it wrote with ``jax.profiler.ProfileData`` alone:

* device busy time: the union of the intervals in which an operation ran
  on a device (the ``XLA Ops`` line of each device plane), clipped to the
  window span, averaged over the devices;
* device time by operation: the HLO instruction's name, which the op
  event's name begins with (``%fusion.12 = f32[...] fusion(...)``);
* the idle gaps between busy intervals, each attributed to the innermost
  benchmark span that covers most of it;
* for each span name, the duration and the device busy time inside each
  occurrence;
* each device's op intervals with their names, from which ``exposed_s``
  takes the time in which a given kind of op runs alone.
"""
from __future__ import annotations

import contextlib
import shutil
import tempfile
from pathlib import Path

import numpy as np

from bench.harness import Spans

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:"
WINDOW = "window"
TOP = 10


def memory_peak(devices) -> int:
    """The peak of allocated bytes on the fullest device, as JAX reports it."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)


@contextlib.contextmanager
def capture(enabled: bool, record):
    """Profile the block when ``enabled``; afterwards ``record.trace`` holds
    the reduction. The trace files live in a temporary directory that is
    removed once read."""
    if not enabled:
        yield
        return
    import jax
    logdir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        record.spans.annotate = True
        jax.profiler.start_trace(logdir)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
            record.spans.annotate = False
        files = sorted(Path(logdir).rglob("*.xplane.pb"))
        if not files:
            raise RuntimeError(f"the profiler wrote no .xplane.pb under {logdir}")
        from jax.profiler import ProfileData
        record.trace = reduce(ProfileData.from_file(str(files[-1])))
    finally:
        shutil.rmtree(logdir, ignore_errors=True)


def _union(intervals: np.ndarray) -> np.ndarray:
    """Disjoint sorted intervals covering the [start, end] rows given."""
    if len(intervals) == 0:
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0])]
    reach = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > reach[:-1]
    starts = iv[new, 0]
    ends = np.append(reach[np.flatnonzero(new)[1:] - 1], reach[-1])
    return np.stack([starts, ends], axis=1)


def _overlap(union: np.ndarray, t0: float, t1: float) -> float:
    """Length of the part of [t0, t1] that ``union`` covers."""
    lo = np.clip(union[:, 0], t0, t1)
    hi = np.clip(union[:, 1], t0, t1)
    return float(np.sum(hi - lo))


def _op_name(event_name: str) -> str:
    return event_name.split(" = ", 1)[0].lstrip("%")


def reduce(profile) -> dict:
    """Reduce a ``ProfileData`` to busy time, op totals, gaps and spans.
    Times are in seconds."""
    spans: list[tuple[str, float, float]] = []
    devices: list[tuple[np.ndarray, list]] = []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs = [(e.start_ns, e.end_ns, _op_name(e.name)) for e in line.events]
                    devices.append((np.array([(a, b) for a, b, _ in evs],
                                             np.float64).reshape(-1, 2) * 1e-9, evs))
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(Spans.PREFIX):
                        spans.append((e.name[len(Spans.PREFIX):],
                                      e.start_ns * 1e-9, e.end_ns * 1e-9))
    windows = [(a, b) for n, a, b in spans if n == WINDOW]
    if not windows or not devices:
        raise RuntimeError(f"trace holds {len(windows)} window spans and "
                           f"{len(devices)} device op lines")
    w0, w1 = windows[0]

    busy, ops, unions = 0.0, {}, []
    for iv, evs in devices:
        u = _union(iv)
        unions.append(u)
        busy += _overlap(u, w0, w1)
        for (a, b, name) in evs:
            d = (min(b, w1 * 1e9) - max(a, w0 * 1e9)) * 1e-9
            if d > 0:
                ops[name] = ops.get(name, 0.0) + d
    busy /= len(devices)

    # idle gaps of the first device, each named by the innermost span that
    # covers most of it
    u = unions[0]
    u = u[(u[:, 1] > w0) & (u[:, 0] < w1)]
    edges = np.concatenate([[w0], np.clip(u.ravel(), w0, w1), [w1]]).reshape(-1, 2)
    inner = [s for s in spans if s[0] != WINDOW]
    names = [n for n, _, _ in inner]
    sa = np.array([a for _, a, _ in inner])
    sb = np.array([b for _, _, b in inner])
    gaps: dict[str, float] = {}
    for g0, g1 in edges[edges[:, 1] > edges[:, 0]]:
        cover = np.minimum(sb, g1) - np.maximum(sa, g0)
        name = "other"
        if len(cover) and cover.max() > 0:
            near = np.flatnonzero(cover >= 0.999 * cover.max())
            name = names[near[np.argmin((sb - sa)[near])]]
        gaps[name] = gaps.get(name, 0.0) + (g1 - g0)

    by_span: dict[str, list[tuple[float, float]]] = {}
    for n, a, b in spans:
        by_span.setdefault(n, []).append(
            (b - a, sum(_overlap(x, a, b) for x in unions) / len(unions)))

    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"window_s": w1 - w0, "busy_s": busy, "ops": ops,
            "spans": by_span,
            "breakdown": {"device_ops": top(ops), "idle_gaps": top(gaps)},
            "window": (w0, w1), "n_devices": len(devices),
            "op_intervals": [(iv, [n for _, _, n in evs]) for iv, evs in devices]}


def exposed_s(reduced: dict, picked) -> float | None:
    """Seconds of the window in which an op that ``picked(name)`` selects
    runs on a device and no other op runs there, averaged over the devices;
    None where no op of the window is selected."""
    w0, w1 = reduced["window"]
    total, found = 0.0, False
    for iv, names in reduced["op_intervals"]:
        sel = np.array([picked(n) for n in names], bool)
        inside = (iv[:, 1] > w0) & (iv[:, 0] < w1)
        if not (sel & inside).any():
            continue
        found = True
        total += _overlap(_union(iv), w0, w1) - _overlap(_union(iv[~sel]), w0, w1)
    return total / len(reduced["op_intervals"]) if found else None
