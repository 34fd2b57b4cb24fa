"""A configuration names its plain reference, a module under ``bench/``, and
the drivers compare with that module, with no edit to any file of the
harness. The program has no ReLU option, so a run of the paper's ELU model
against the test-only ReLU reference reads not correct; what is shown is
that the run loaded the named module and compared its readings."""
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import compare, flops, harness, reference
from bench.tests.conftest import run_tiny, tiny, tiny_mix, with_r4

RELU = "tests/data/relu_reference.py"
SEED = 2**31 + 29


def test_a_configuration_names_its_reference(bench):
    conf = tiny(bench, "large-train-r1")
    assert harness.reference_module(conf) is reference
    ref = harness.reference_module(dict(conf, reference=RELU))
    assert ref.__file__ == str(harness.BENCH_DIR / RELU)
    x = jax.numpy.array([-1.0, 2.0])
    np.testing.assert_array_equal(ref._elu(x), [0.0, 2.0])
    assert float(reference._elu(x)[0]) < 0


def test_a_run_compares_with_the_named_reference(bench, monkeypatch):
    conf = dict(tiny(bench, "large-train-r1"), reference=RELU)
    compared = []
    gaps = compare.train_gaps
    monkeypatch.setattr(compare, "train_gaps",
                        lambda prog, ref: compared.append(ref) or gaps(prog, ref))
    res = run_tiny(bench, "large-train-r1", seed=SEED, config=conf)
    assert not res["correct"], res["checks"]

    drv, mix = harness.driver("train"), tiny_mix(bench, "large-train-r1")
    devices = jax.devices("cpu")[:1]
    relu = drv.reference_steps(conf, mix, SEED, drv.reference_graph(conf, mix, devices))
    plain_conf = tiny(bench, "large-train-r1")
    elu = drv.reference_steps(plain_conf, mix, SEED,
                              drv.reference_graph(plain_conf, mix, devices))
    (losses, _, _), = compared
    assert losses == relu[0] and losses != elu[0]


def test_flops_come_from_the_reference_where_it_counts_them(bench):
    drv = harness.driver("train")
    conf, mix = tiny(bench, "large-train-r1"), tiny_mix(bench, "large-train-r1")
    counts = SimpleNamespace(train_step_flops=lambda model, nodes, edges, batch: 7 * nodes)
    assert drv.step_flops(conf, mix, counts) == 7 * 125
    assert drv.step_flops(conf, mix, SimpleNamespace()) == flops.train_step_flops(
        conf["model"], *flops.box_graph_size((2, 2, 2), 2), mix["batch"])


@pytest.mark.parametrize("cell", [w["name"] for w in with_r4(harness.load_benchmark())["workloads"]])
def test_where_each_cells_reference_runs(bench, cell):
    """A cell on one chip runs its reference on JAX's default device as
    before; a cell on more splits the reference's edges over its chips. The
    test process has one CPU device, so it stands in for each of a cell's."""
    wl = harness.workload(bench, cell)
    conf, mix = tiny(bench, cell), tiny_mix(bench, cell)
    drv = harness.driver(mix["kind"])
    if wl["chips"] > 1:
        mesh = drv.reference_mesh(jax.devices("cpu")[:1] * wl["chips"])
        assert mesh.axis_names == ("edges",) and mesh.size == wl["chips"]
        return
    if mix["kind"] == "train":
        assert drv.reference_mesh(jax.devices("cpu")[:1]) is None
        graph = drv.reference_graph(conf, mix, jax.devices("cpu")[:1])
    else:
        graph = drv.reference_graph(conf, mix)
    assert not isinstance(graph.arrays["src"].sharding, jax.sharding.NamedSharding)
