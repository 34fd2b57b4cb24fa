"""The plain reference against the program at a tiny mesh, both
configurations: the same seed gives the same weights, and the forward pass,
loss and gradients agree to float32 rounding. The reference imports nothing
of the program."""
import json
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from bench import reference
from bench.harness import ROOT

MODELS = {size: json.loads((ROOT / "bench" / "configs" / f"paper-{size}.json").read_text())["model"]
          for size in ("large", "small")}


def test_reference_imports_nothing_of_the_program():
    code = ("import sys; import bench.reference; "
            "bad = [m for m in sys.modules if m.split('.')[0] == 'repro']; "
            "assert not bad, bad")
    subprocess.run([sys.executable, "-c", code], cwd=ROOT, check=True, timeout=120)


def test_lattice_matches_the_program_mesh():
    from repro.core import box_mesh, gll_points, partition_mesh
    sem = box_mesh((2, 2, 3), p=3)
    coords, src, dst = reference.box_lattice((2, 2, 3), 3)
    np.testing.assert_array_equal(coords, sem.coords)
    np.testing.assert_array_equal(reference.gll_points(5), gll_points(5))
    pg = partition_mesh(sem, (1, 1, 1))
    n = int(pg.edge_mask.sum())
    assert sorted(zip(src, dst)) == sorted(zip(pg.edge_src[0, :n], pg.edge_dst[0, :n]))


@pytest.mark.parametrize("name", sorted(MODELS))
def test_reference_matches_program(name):
    from repro.core import GNNConfig, box_mesh, init_gnn, partition_mesh
    from repro.core.distributed import make_gnn_step_fns, shard_graph
    from repro.core.graph_state import NMPPlan, ShardedGraph
    from repro.core.partition import gather_node_features
    from repro.launch.mesh import make_mesh

    model = MODELS[name]
    cfg = GNNConfig(**model)
    sem = box_mesh((2, 2, 2), p=2)
    pg = partition_mesh(sem, (1, 1, 1))
    mesh = make_mesh((1, 1), ("data", "graph"))
    eval_step, _, grad_step, _ = make_gnn_step_fns(mesh, cfg, NMPPlan())
    gs = shard_graph(mesh, ShardedGraph.build(pg, sem.coords, NMPPlan()))
    graph = reference.Graph((2, 2, 2), 2)

    key = jax.random.PRNGKey(2**31 + 3)
    params = init_gnn(key, cfg)
    ref_params = reference.init_params(key, model)
    assert jax.tree.structure(params) == jax.tree.structure(ref_params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(ref_params)):
        np.testing.assert_array_equal(a, b)

    field = reference.taylor_green(graph.coords, 0.35, 0.01)
    x = jnp.asarray(gather_node_features(pg, field)[None])
    y = np.asarray(eval_step(params, x, gs))[0, 0, :sem.n_nodes]
    y_ref = np.asarray(reference.forward(ref_params, jnp.asarray(field), graph.arrays))
    np.testing.assert_allclose(y, y_ref, rtol=0, atol=2e-5 * np.abs(y_ref).max())

    loss, grads = grad_step(params, x, x, gs)
    ref_loss, ref_grads = jax.value_and_grad(reference.loss)(
        ref_params, jnp.asarray(field)[None], graph.arrays)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=2e-6)
    scale = max(float(jnp.abs(g).max()) for g in jax.tree.leaves(ref_grads))
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-5 * scale)


def test_bf16_3x_control_rounds_like_three_bf16_passes():
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 96))
    w = jax.random.normal(jax.random.PRNGKey(1), (96, 32))
    exact = np.asarray(x, np.float64) @ np.asarray(w, np.float64)
    hi = np.asarray(reference.dot(x, w, reference.HIGHEST))
    ctrl = np.asarray(reference.dot(x, w, reference.BF16_3X))
    err_hi, err_ctrl = np.abs(hi - exact).max(), np.abs(ctrl - exact).max()
    assert err_ctrl > 3 * err_hi
    assert err_ctrl < 1e-3 * np.abs(exact).max()
