"""The control: the plain reference computed with three bf16 passes per
float32 product (the algorithm of ``Precision.HIGH``, the step below the
configuration's ``HIGHEST``) in the program's place fails at least one of
the cell's compared numbers at their committed limits, at a tiny mesh on
the CPU. On the chip the limits were set against the chip's own
``Precision.HIGH`` at the cells' sizes (``bench/calibrate.py``)."""
import json

import jax
import pytest

from bench import compare, harness, reference
from bench.tests.conftest import limits as committed_limits, tiny, tiny_mix

SEEDS = [2**31 + 101, 3 * 10**9 + 7, 12345]


def control_readings(bench, cell: str, seed: int) -> dict:
    conf, mix = tiny(bench, cell), tiny_mix(bench, cell)
    drv = harness.driver(mix["kind"])
    if mix["kind"] == "train":
        graph = drv.reference_graph(conf, mix, jax.devices("cpu")[:1])
        want = drv.reference_steps(conf, mix, seed, graph)
        got = drv.reference_steps(conf, mix, seed, graph, precision=reference.BF16_3X)
        return compare.train_gaps(got, want)
    graph = drv.reference_graph(conf, mix)
    pool = dict(enumerate(drv.fields(conf, mix, graph.coords, seed)))
    want = drv.reference_predictions(conf, seed, pool, graph)
    got = drv.reference_predictions(conf, seed, pool, graph, reference.BF16_3X)
    return {"pred_gap": max(compare.prediction_gap(got[k], want[k]) for k in pool)}


#: cells on one chip; a cell on more computes at the tiny mesh the very
#: reference of the one-chip cell of its configuration (the reference ignores
#: the rank grid), and its limits are held against the chip's control by
#: ``test_limits_lie_between_the_recorded_chip_readings``
ONE_CHIP = [w["name"] for w in harness.load_benchmark()["workloads"] if w["chips"] == 1]


@pytest.mark.parametrize("cell", ONE_CHIP)
@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails_a_limit(bench, cell, seed):
    limits = committed_limits(cell)
    readings = control_readings(bench, cell, seed)
    assert any(readings[k] > limits[k] for k in limits), (readings, limits)


@pytest.mark.parametrize("cell", [w["name"] for w in harness.load_benchmark()["workloads"]])
def test_limits_lie_between_the_recorded_chip_readings(cell):
    """Each committed limit lies above the largest sound reading of the
    program on the chip and below its upper reading: the smallest of the
    chip's control, where that is three times the program's or more, and of
    each fault that reads ten times it or more. The control reads above at
    least one of the cell's limits."""
    limits = json.loads((harness.BENCH_DIR / "limits" / f"{cell}.json").read_text())
    summary = json.loads(
        (harness.BENCH_DIR / "calibration" / f"{cell}.json").read_text())["summary"]
    assert all(summary["program"][k] < limits[k] for k in limits)
    assert any(summary["control_high"][k] > limits[k] for k in limits)
    for k in limits:
        lower = summary["program"][k]
        upper = [r[k] for kind, r in summary.items()
                 if r[k] >= (3 if kind == "control_high" else 10) * lower and kind != "program"]
        assert upper and min(upper) > limits[k]
