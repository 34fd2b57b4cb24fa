"""Autoregressive rollout training (repro.train.rollout): the paper's
consistency guarantee extended to K chained forwards.

The load-bearing assertion: the K=3 rollout loss, per-step predictions AND
parameter gradients are identical between 1 rank and a 4-partition graph —
for BOTH halo/compute schedules (blocking / overlap).  Each rollout step
feeds the model its own previous prediction, so any halo inconsistency
compounds geometrically; this is the sharpest consistency test in the
suite.  The real-collective shard_map rollout is exercised by the
subprocess driver at the bottom and by the CI consistency-matrix job.
"""
import os
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (
    A2A, NONE, GNNConfig, HaloSpec, NMPPlan, ShardedGraph, box_mesh,
    init_gnn, partition_mesh, gather_node_features, taylor_green_velocity,
)
from repro.core.partition import scatter_node_outputs
from repro.core.reference import rollout_stacked

K = 3
DT = 0.05


def _case():
    mesh = box_mesh((4, 2, 2), p=2)
    cfg = GNNConfig(hidden=8, n_mp_layers=2, mlp_hidden_layers=2)
    params = init_gnn(jax.random.PRNGKey(0), cfg)
    return mesh, cfg, params


def _sequences(pg, mesh):
    x0 = jnp.asarray(gather_node_features(
        pg, taylor_green_velocity(mesh.coords)))
    tgts = jnp.stack([
        jnp.asarray(gather_node_features(
            pg, taylor_green_velocity(mesh.coords, t=(k + 1) * DT)))
        for k in range(K)])
    return x0, tgts


def _rollout(mesh, cfg, params, grid, mode, schedule, noise_global=None):
    pg = partition_mesh(mesh, grid)
    plan = NMPPlan.build(pg, mode, schedule=schedule)
    graph = ShardedGraph.build(pg, mesh.coords, plan)
    x0, tgts = _sequences(pg, mesh)
    noise = None
    if noise_global is not None:
        noise = jnp.asarray(gather_node_features(pg, noise_global))

    def f(p):
        return rollout_stacked(p, x0, tgts, graph, plan, cfg.node_out,
                               noise=noise)
    (loss, preds), grads = jax.value_and_grad(f, has_aux=True)(params)
    preds_g = np.stack([scatter_node_outputs(pg, np.asarray(preds[k]))
                        for k in range(K)])
    return float(loss), preds_g, grads


def _assert_preds_consistent(p, p_ref):
    """Per-step predictions agree up to fp32 summation order.

    Partitioning only reorders the fp32 sums of each node's aggregate, and
    that rounding is relative to the magnitude of the field being summed,
    not to each output element: an element that happens to sit near zero
    carries the same absolute error as its neighbours. So step k is bounded
    in the max norm relative to the field's max norm, with a bound that
    grows 3x per step because each step feeds its rounding back into the
    next. (Measured on the (4, 2, 2) p=2 mesh: 5e-7, 4e-6, 1.2e-5 for
    k = 1, 2, 3; dropping the halo exchange gives errors as large as the
    field itself.)
    """
    for k in range(p_ref.shape[0]):
        scale = float(np.abs(p_ref[k]).max())
        err = float(np.abs(p[k] - p_ref[k]).max())
        assert err <= 1e-5 * 3 ** k * scale, (k, err, scale)


def _grad_rel_err(a, b):
    na = float(jnp.sqrt(sum(jnp.sum(jnp.square(x))
                            for x in jax.tree.leaves(a))))
    nd = float(jnp.sqrt(sum(jnp.sum(jnp.square(x - y)) for x, y in
                            zip(jax.tree.leaves(a), jax.tree.leaves(b)))))
    return nd / max(na, 1e-12)


@pytest.mark.parametrize("schedule", ["blocking", "overlap"])
@pytest.mark.parametrize("grid", [(4, 1, 1), (2, 2, 1)])
def test_rollout_consistency_1_vs_4_ranks(schedule, grid):
    """K=3 rollout: loss, per-step predictions and parameter gradients are
    identical between 1 rank and a 4-partition graph, both schedules."""
    mesh, cfg, params = _case()
    l1, p1, g1 = _rollout(mesh, cfg, params, (1, 1, 1), NONE, schedule)
    l4, p4, g4 = _rollout(mesh, cfg, params, grid, A2A, schedule)
    assert abs(l4 - l1) < 2e-6 * max(1.0, abs(l1)), (grid, schedule)
    _assert_preds_consistent(p4, p1)
    # K chained forwards amplify fp32 summation-order noise elementwise, so
    # gradients are compared by relative norm (loss/value agreement above is
    # the bitwise-level check)
    assert _grad_rel_err(g1, g4) < 5e-4, (grid, schedule)


def test_rollout_blocking_matches_overlap():
    """The two schedules are arithmetically identical through the K-step
    feedback loop as well."""
    mesh, cfg, params = _case()
    lb, pb, gb = _rollout(mesh, cfg, params, (2, 2, 1), A2A, "blocking")
    lo, po, go = _rollout(mesh, cfg, params, (2, 2, 1), A2A, "overlap")
    assert abs(lo - lb) < 1e-6 * max(1.0, abs(lb))
    _assert_preds_consistent(po, pb)
    assert _grad_rel_err(gb, go) < 5e-4


def test_rollout_without_halo_deviates():
    """Dropping the exchange breaks the K-step rollout harder than the
    single-step forward — the inconsistency is fed back K times."""
    mesh, cfg, params = _case()
    l1, _, _ = _rollout(mesh, cfg, params, (1, 1, 1), NONE, "blocking")
    ln, _, _ = _rollout(mesh, cfg, params, (2, 2, 1), NONE, "blocking")
    assert abs(ln - l1) > 1e-6


def test_pushforward_noise_consistent_and_stop_grad():
    """Pushforward noise: (a) perturbing the initial state stays 1-rank ==
    4-rank consistent when the noise is drawn on the global field, (b) the
    perturbation actually changes the loss, and (c) gradients do not flow
    through the noised state (stop_gradient): d loss / d noise == 0."""
    mesh, cfg, params = _case()
    rng = np.random.default_rng(0)
    nz = rng.normal(size=(mesh.n_nodes, cfg.node_in)).astype(np.float32) * 0.05
    l1, p1, g1 = _rollout(mesh, cfg, params, (1, 1, 1), NONE, "blocking",
                          noise_global=nz)
    l4, p4, g4 = _rollout(mesh, cfg, params, (2, 2, 1), A2A, "blocking",
                          noise_global=nz)
    assert abs(l4 - l1) < 2e-6 * max(1.0, abs(l1))
    _assert_preds_consistent(p4, p1)
    assert _grad_rel_err(g1, g4) < 5e-4
    # the noise engaged
    l0, _, _ = _rollout(mesh, cfg, params, (1, 1, 1), NONE, "blocking")
    assert abs(l1 - l0) > 1e-7
    # stop_gradient: the loss is insensitive to the noise argument
    pg = partition_mesh(mesh, (1, 1, 1))
    plan = NMPPlan(halo=HaloSpec(mode=NONE))
    graph = ShardedGraph.build(pg, mesh.coords, plan)
    x0, tgts = _sequences(pg, mesh)
    noise = jnp.asarray(gather_node_features(pg, nz))
    g_noise = jax.grad(lambda n: rollout_stacked(
        params, x0, tgts, graph, plan, cfg.node_out, noise=n)[0])(noise)
    assert float(jnp.abs(g_noise).max()) == 0.0


def test_rollout_gradient_flows_through_every_step():
    """BPTT sanity: a loss depending ONLY on the final step still reaches
    the parameters — gradients flow through the scan over the model's own
    predictions (no accidental stop_gradient between steps)."""
    mesh, cfg, params = _case()
    pg = partition_mesh(mesh, (1, 1, 1))
    plan = NMPPlan(halo=HaloSpec(mode=NONE))
    graph = ShardedGraph.build(pg, mesh.coords, plan)
    x0, tgts = _sequences(pg, mesh)

    def last_step_loss(p):
        _, preds = rollout_stacked(p, x0, tgts, graph, plan, cfg.node_out)
        return jnp.sum((preds[-1] - tgts[-1]) ** 2)

    g = jax.grad(last_step_loss)(params)
    gn = float(jnp.sqrt(sum(jnp.sum(x ** 2) for x in jax.tree.leaves(g))))
    assert np.isfinite(gn) and gn > 0
    # and the K-step predictions genuinely differ from repeating step 1
    _, preds = rollout_stacked(params, x0, tgts, graph, plan, cfg.node_out)
    assert float(jnp.abs(preds[2] - preds[0]).max()) > 1e-6


def test_rollout_shard_map_collective_path_subprocess():
    """The jitted production rollout on REAL collectives (4 host devices),
    both partition grids x both halo modes, vs the stacked oracle."""
    driver = os.path.join(os.path.dirname(__file__), "drivers",
                          "rollout_driver.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, driver], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, \
        f"driver failed:\n{res.stdout[-3000:]}\n{res.stderr[-3000:]}"
    assert "ROLLOUT DRIVER PASS" in res.stdout


def test_rollout_curriculum_and_noise_annealing():
    """TrainConfig.rollout_curriculum splits the run into even stages of
    increasing K (1 -> 2 here) and pushforward_noise_final anneals the
    stop-grad noise linearly; the smoke run must record the staged K per
    step and produce finite losses on the single-device mesh."""
    from repro.launch.mesh import make_mesh
    from repro.train.loop import TrainConfig, train_consistent_gnn

    mesh = box_mesh((2, 2, 2), p=2)
    cfg = GNNConfig(hidden=8, n_mp_layers=1, mlp_hidden_layers=2)
    pg = partition_mesh(mesh, (1, 1, 1))
    mesh_dev = make_mesh((1, 1), ("data", "graph"))
    tcfg = TrainConfig(n_steps=4, batch=1, halo_mode="none", log_every=100,
                       rollout_curriculum=(1, 2),
                       pushforward_noise=0.01, pushforward_noise_final=0.0)
    hist = train_consistent_gnn(mesh_dev, pg, mesh, cfg, tcfg)
    assert hist["rollout_k"] == [1, 1, 2, 2]
    assert all(np.isfinite(loss) for loss in hist["losses"])
    assert hist["schedule"] == "blocking"
