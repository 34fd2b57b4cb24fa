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


def test_reduce_keeps_each_devices_op_intervals():
    r = trace.reduce(synthetic())
    assert r["n_devices"] == 1 and r["window"] == pytest.approx((0.0, 10e-6))
    (iv, names), = r["op_intervals"]
    np.testing.assert_allclose(iv, [[1e-6, 3e-6], [2e-6, 4e-6], [6e-6, 7e-6]])
    assert names == ["fusion.1", "scatter.2", "fusion.1"]


def collectives_profile():
    """Two devices over a window of 20 us. Device 0: a collective hidden
    under compute ([2, 4] inside [1, 5]), one half exposed ([8, 12] against
    compute in [6, 10]), and one alone ([14, 15]). Device 1: one collective
    alone ([3, 6]) and compute in [10, 11]."""
    dev0 = _plane(1, "/device:TPU:0", "XLA Ops", [
        ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 1, 5),
        ("%all-reduce-start.2 = f32[8]{0} all-reduce-start(f32[8]{0} %a)", 2, 4),
        ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 6, 10),
        ("%collective-permute-done.4 = f32[8]{0} collective-permute-done(f32[8]{0} %a)", 8, 12),
        ("%all-reduce-done.2 = f32[8]{0} all-reduce-done(f32[8]{0} %a)", 14, 15)])
    dev1 = _plane(3, "/device:TPU:1", "XLA Ops", [
        ("%all-reduce-done.2 = f32[8]{0} all-reduce-done(f32[8]{0} %a)", 3, 6),
        ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 10, 11)])
    host = _plane(2, "/host:CPU", "python", [("bench:window", 0, 20)])
    return ProfileData.from_text_proto(dev0 + dev1 + host)


def test_exposed_time_of_collectives_hidden_half_exposed_and_alone():
    r = trace.reduce(collectives_profile())
    picked = lambda name: not name.startswith("fusion")  # noqa: E731
    # device 0: 0 (hidden) + 2 (half of [8, 12]) + 1 (alone); device 1: 3
    assert trace.exposed_s(r, picked) == pytest.approx((3e-6 + 3e-6) / 2)
    assert trace.exposed_s(r, lambda name: False) is None
    # the outputs that were there before stay as they were: busy time is the
    # mean of the devices' unions, op time the sum over devices
    assert r["busy_s"] == pytest.approx((4e-6 + 6e-6 + 1e-6 + 3e-6 + 1e-6) / 2)
    assert r["ops"]["fusion.3"] == pytest.approx(5e-6)


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
