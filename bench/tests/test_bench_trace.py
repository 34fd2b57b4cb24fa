"""The reduction of a profiler trace to busy time, op totals, idle gaps named
by the benchmark's spans, and per-span busy time."""
import numpy as np
import pytest
from jax.profiler import ProfileData

from bench import trace

US = 1_000_000   # picoseconds


def _plane(pid, name, line, events):
    names = sorted({n for n, *_ in events})
    meta = {n: i + 1 for i, n in enumerate(names)}
    evs = [f"events {{ metadata_id: {meta[n]} offset_ps: {t0 * US} "
           f"duration_ps: {(t1 - t0) * US} }}" for n, t0, t1 in events]
    md = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                  for n, i in meta.items())
    return (f'planes {{ id: {pid} name: "{name}" lines {{ id: {pid} name: "{line}" '
            f'timestamp_ns: 0 {" ".join(evs)} }} {md} }}')


def synthetic():
    """A window of 10 us: ops busy in [1, 4] and [6, 7] (one op overlapping
    another), idle [0, 1] under batch+grad_dispatch, [4, 6] under update
    inside loss_fetch's parent step, and [7, 10] under no span."""
    device = _plane(1, "/device:TPU:0", "XLA Ops", [
        ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 1, 3),
        ("%scatter.2 = f32[8]{0} scatter(f32[8]{0} %a)", 2, 4),
        ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 6, 7)])
    host = _plane(2, "/host:CPU", "python", [
        ("bench:window", 0, 10), ("bench:batch+grad_dispatch", 0, 1),
        ("bench:step", 0, 7), ("bench:update", 4, 6), ("other thread work", 7, 9)])
    return ProfileData.from_text_proto(device + host)


def test_reduce_synthetic_trace():
    r = trace.reduce(synthetic())
    assert r["window_s"] == pytest.approx(10e-6)
    assert r["busy_s"] == pytest.approx(4e-6)
    assert r["ops"] == pytest.approx({"fusion.1": 3e-6, "scatter.2": 2e-6})
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert gaps == pytest.approx({"batch+grad_dispatch": 1e-6, "update": 2e-6, "other": 3e-6})
    assert r["breakdown"]["device_ops"][0] == ["fusion.1", pytest.approx(3e-6)]
    (dur, busy), = r["spans"]["step"]
    assert dur == pytest.approx(7e-6) and busy == pytest.approx(4e-6)


def test_union_merges_overlaps():
    iv = np.array([[5.0, 6.0], [0.0, 2.0], [1.0, 3.0], [3.0, 4.0], [7.0, 7.5]])
    np.testing.assert_array_equal(trace._union(iv), [[0.0, 4.0], [5.0, 6.0], [7.0, 7.5]])


def test_trace_without_a_window_span_is_an_error():
    device = _plane(1, "/device:TPU:0", "XLA Ops", [("fusion.1", 1, 3)])
    with pytest.raises(RuntimeError, match="window"):
        trace.reduce(ProfileData.from_text_proto(device))


def test_reduce_recorded_tpu_trace():
    """A trace recorded on a TPU v5 lite: three calls of a small jitted step
    under the benchmark's spans (``bench:window``, ``bench:batch+grad_dispatch``,
    ``bench:loss_fetch``)."""
    import gzip
    from pathlib import Path
    raw = gzip.decompress((Path(__file__).parent / "data" / "tiny.xplane.pb.gz").read_bytes())
    r = trace.reduce(ProfileData.from_serialized_xspace(raw))
    assert 0 < r["busy_s"] < r["window_s"]
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert set(gaps) <= {"batch+grad_dispatch", "loss_fetch", "other"}
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    assert r["ops"] and all(" " not in name for name in r["ops"])
    assert len(r["spans"]["batch+grad_dispatch"]) == len(r["spans"]["loss_fetch"]) == 3
