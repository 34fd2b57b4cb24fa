"""Production distributed GNN steps: shard_map over ('data', 'graph') axes.

Layout (matches the paper's Frontier runs, adapted to a TPU mesh):
  * 'graph' axis — the paper's spatial decomposition: R sub-graphs of one
    mesh-based graph; halo ppermute/all_to_all traffic lives ONLY here
    (intra-pod ICI).
  * 'data' axis — DDP over snapshots (batches of time steps on the same
    mesh); gradients are psum'ed over ('data', 'graph', ['pod']).
  * optional 'pod' axis — pure data parallelism across pods; only gradient
    all-reduce crosses the inter-pod links.

Inputs per device: x, y_hat blocks [B_local, N_pad, F]; the static
:class:`~repro.core.graph_state.ShardedGraph` is sharded over 'graph' via
its own ``specs(graph_axis)`` (identical for all data replicas), and the
execution policy — incl. the per-level halo specs — is one
:class:`~repro.core.graph_state.NMPPlan`.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.consistent_loss import consistent_mse
from repro.core.gnn import GNNConfig, gnn_forward
from repro.core.graph_state import NMPPlan, as_graph


def make_gnn_step_fns(
    mesh: Mesh,
    cfg: GNNConfig,
    plan: NMPPlan,
    data_axes: Sequence[str] = ("data",),
    graph_axis: str = "graph",
    learning_rate: float = 1e-3,
):
    """Build jit'd (eval_step, loss_step, grad_step, train_step) closed over
    mesh + plan.

    train_step here is plain SGD for consistency experiments; the full
    training loop (AdamW etc.) lives in repro.train and reuses grad_step.

    Multilevel models (``cfg.n_levels > 1``) need a plan whose
    ``coarse_halos`` carry one HaloSpec per coarse level
    (``NMPPlan.build(hierarchy, mode, ...)``) and a graph built with the
    hierarchy (``ShardedGraph.build(pg, coords, plan, hierarchy=...)``).
    """
    del cfg  # architecture is entirely encoded in the params pytree
    all_axes = tuple(data_axes) + (graph_axis,)

    def forward_local(params, x, graph):
        # x arrives as [B_local, 1, N_pad, F] (graph axis sharded to size 1)
        g = graph.rank_local()
        y = gnn_forward(params, x[:, 0], g, plan)
        return y[:, None]

    def loss_local(params, x, y_hat, graph):
        g = graph.rank_local()
        x, y_hat = x[:, 0], y_hat[:, 0]
        y = gnn_forward(params, x, g, plan)
        # consistent over the graph axis (Eq. 6), mean over data axes
        with jax.named_scope("loss"):
            loss = consistent_mse(y, y_hat, g["node_inv_mult"],
                                  axis_names=(graph_axis,))
            if data_axes:
                loss = jax.lax.pmean(loss, tuple(data_axes))
        return loss, y

    def grad_local(params, x, y_hat, graph):
        (loss, y), grads = jax.value_and_grad(loss_local, has_aux=True)(
            params, x, y_hat, graph)
        # The local backward of the replicated loss computes, on device q,
        # d(sum over ALL devices of the replicated scalar)/d theta_q
        # = n_dev * dL/d theta_q  (theta paths local to q, incl. halo routes).
        # pmean over every axis therefore yields exactly dL/d theta.
        with jax.named_scope("grad_sync"):
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, all_axes), grads)
        return loss, grads

    def _wrap(fn, out_specs, n_feature_args):
        def call(params, *args):
            graph = as_graph(args[-1])
            in_specs = (
                P(),  # params replicated
                *(P(tuple(data_axes), graph_axis, None, None)
                  for _ in range(n_feature_args)),
                graph.specs(graph_axis),
            )
            return jax.shard_map(
                fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False,
            )(params, *args)
        return jax.jit(call)

    eval_step = _wrap(forward_local, P(tuple(data_axes), graph_axis, None, None), 1)
    loss_step = _wrap(lambda p, x, y, g: loss_local(p, x, y, g)[0], P(), 2)

    def train_local(params, x, y_hat, graph):
        loss, grads = grad_local(params, x, y_hat, graph)
        new_params = jax.tree.map(lambda p, g: p - learning_rate * g, params, grads)
        return loss, new_params

    def _wrap_pair(fn, donate=False):
        def call(params, x, y_hat, graph):
            graph = as_graph(graph)
            in_specs = (
                P(),
                P(tuple(data_axes), graph_axis, None, None),
                P(tuple(data_axes), graph_axis, None, None),
                graph.specs(graph_axis),
            )
            return jax.shard_map(
                fn, mesh=mesh,
                in_specs=in_specs, out_specs=(P(), P()),
                check_vma=False,
            )(params, x, y_hat, graph)
        return jax.jit(call, donate_argnums=(0,) if donate else ())

    train_step = _wrap_pair(train_local, donate=True)
    grad_step = obs.program("grad_step", _wrap_pair(grad_local))

    return eval_step, loss_step, grad_step, train_step


def shard_graph(mesh: Mesh, graph, graph_axis="graph"):
    """Place the static ShardedGraph with its own shardings — once per run;
    the graph is loop-invariant, so keep the result across steps."""
    graph = as_graph(graph)
    return jax.device_put(
        graph,
        jax.tree.map(lambda s: NamedSharding(mesh, s), graph.specs(graph_axis),
                     is_leaf=lambda v: isinstance(v, P)))


def shard_inputs(mesh: Mesh, x, graph, data_axes=("data",), graph_axis="graph"):
    """Place host arrays with the step-function shardings."""
    xs = jax.device_put(x, NamedSharding(mesh, P(tuple(data_axes), graph_axis, None, None)))
    return xs, shard_graph(mesh, graph, graph_axis)
