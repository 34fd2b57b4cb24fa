"""The peak table and the FLOP counter: the arithmetic the metrics rest on."""
import json

import pytest

from bench import flops, peaks
from bench.harness import BENCH_DIR

LARGE = json.loads((BENCH_DIR / "configs" / "paper-large.json").read_text())["model"]
SMALL = json.loads((BENCH_DIR / "configs" / "paper-small.json").read_text())["model"]


def test_peaks_known_kind_and_unknown_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["ici_bytes_per_s"] == 200e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("cpu")


@pytest.mark.parametrize("model,built", [(LARGE, 91_555), (SMALL, 4_003)])
def test_param_count_matches_what_the_program_builds(model, built):
    import jax
    from repro import nn
    from repro.core import GNNConfig, init_gnn
    params = init_gnn(jax.random.PRNGKey(0), GNNConfig(**model))
    assert flops.param_count(model) == nn.count_params(params) == built


@pytest.mark.parametrize("model,table_i", [(LARGE, 91_459), (SMALL, 3_979)])
def test_table_i_counts_are_the_configs_with_four_edge_inputs(model, table_i):
    assert flops.param_count(dict(model, edge_in=4)) == table_i
    # one hidden-to-hidden layer fewer in each of the 11 MLPs gives the
    # count of GNNConfig.large() / small()
    h = model["hidden"]
    shallow = dict(model, mlp_hidden_layers=model["mlp_hidden_layers"] - 1)
    assert flops.param_count(shallow) == flops.param_count(model) - 11 * (h * h + h)


def test_box_graph_size_and_flops_at_the_cell_size():
    nodes, edges = flops.box_graph_size((8, 8, 8), 8)
    assert (nodes, edges) == (274_625, 1_622_400)
    # the four edge MLPs: 18,432 FLOPs per edge each
    assert 2 * (96 * 32 + 6 * 32 * 32) == 18_432
    assert flops.forward_flops(LARGE, nodes, edges) == 165_131_657_600
    assert flops.train_step_flops(LARGE, nodes, edges) == 3 * 165_131_657_600


def test_box_graph_size_matches_the_program_mesh():
    from repro.core import box_mesh, partition_mesh
    sem = box_mesh((2, 3, 1), p=3)
    pg = partition_mesh(sem, (1, 1, 1))
    assert flops.box_graph_size((2, 3, 1), 3) == (sem.n_nodes, int(pg.edge_mask.sum()))
