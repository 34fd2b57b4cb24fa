"""Multi-step autoregressive rollout training on the ShardedGraph/NMPPlan API.

One-step training teaches a mesh surrogate to predict t -> t+dt from ground
truth; deployed autoregressively it feeds its OWN predictions back, and the
distribution shift compounds.  X-MeshGraphNet (Nabian et al., 2024) and the
SCALES line of work (Bartoldson et al., 2023) show the fix is to train the
way you roll out: unroll K model steps inside the loss (gradients flow
through the model's own predictions) and optionally perturb the initial
state with *pushforward noise* — a stop-gradient perturbation that emulates
accumulated rollout error without letting the optimizer exploit it.

Everything here preserves the paper's consistency guarantee: each of the K
steps is the full halo-consistent forward, each per-step loss is the
Eq. 6 consistent MSE, so the K-step rollout loss and its parameter
gradients are identical between 1 rank and any R-rank partition (asserted
by ``tests/test_rollout.py`` and ``tests/drivers/rollout_driver.py`` for
both halo/compute schedules, and by ``benchmarks/rollout.py`` on every
bench run).

Shapes (stacked, host side):
  x0       [B, R, N_pad, F]     initial state
  targets  [B, K, R, N_pad, F]  ground-truth states t+1 .. t+K
  noise    [B, R, N_pad, F]     pushforward perturbation (zeros to disable);
                                must be identical across coincident copies —
                                generate on the global node field and
                                ``gather_node_features`` it.

Deterministic-replay contract (elastic resume, CONTRIBUTING.md): every
batch function here is PURE in ``step`` — snapshot times are
``(step*batch + b)*dt`` and noise is drawn from a fresh
``default_rng(seed + step*batch + b)`` — so a run restored from a step-k
checkpoint replays steps k+1.. with exactly the batches the uninterrupted
run saw.  Curriculum state is equally replayable: :func:`curriculum_k` maps
``step`` to its rollout depth as a pure function, never as mutable loop
state.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.consistent_loss import consistent_mse
from repro.core.gnn import GNNConfig, gnn_forward
from repro.core.graph_state import NMPPlan, as_graph
from repro.core.mesh_gen import SEMMesh, taylor_green_velocity
from repro.core.partition import PartitionedGraphs, gather_node_features


def curriculum_k(stages: Sequence[int], n_steps: int, step: int) -> int:
    """Rollout depth K for ``step`` under a staged curriculum.

    ``stages`` (e.g. ``(1, 2, 4)``) split ``n_steps`` into even stages of
    increasing K.  Pure in ``step`` — part of the deterministic-replay
    contract: an elastically resumed run recomputes the same K schedule the
    original run used instead of carrying it as loop state.
    """
    stages = tuple(stages)
    if not stages:
        return 1
    stage_len = max(1, -(-n_steps // len(stages)))
    return stages[min(step // stage_len, len(stages) - 1)]


def rollout_step(params, x0, targets, graph, plan: NMPPlan,
                 noise=None, axis_names: Sequence[str] = ()):
    """Rank-local K-step autoregressive rollout (jit/scan-compiled core).

    Scans the consistent GNN over its own predictions: step k consumes the
    step k-1 output, and every step's halo-consistent MSE against
    ``targets[k]`` enters the mean.  ``noise`` (pushforward) perturbs only
    the step-1 input, wrapped in ``stop_gradient`` so no gradient flows
    through the noised state's perturbation.  Returns (mean per-step loss,
    predictions [K, ..., N_pad, F]).

    ``x0``: [N_pad, F] or [B, N_pad, F]; ``targets``: [K, ...x0 shape...].
    """
    graph = as_graph(graph)
    g0 = graph.levels[0]
    x = x0
    if noise is not None:
        x = x + jax.lax.stop_gradient(noise)

    def body(carry, tgt):
        y = gnn_forward(params, carry, graph, plan)
        with jax.named_scope("loss"):
            loss_k = consistent_mse(y, tgt, g0["node_inv_mult"],
                                    axis_names=axis_names)
        return y, (loss_k, y)

    _, (losses, preds) = jax.lax.scan(body, x, targets)
    return losses.mean(), preds


def make_rollout_step_fns(
    mesh: Mesh,
    cfg: GNNConfig,
    plan: NMPPlan,
    rollout_steps: int,
    data_axes: Sequence[str] = ("data",),
    graph_axis: str = "graph",
):
    """Build jit'd (rollout_eval, rollout_grad) over a ('data','graph') mesh.

    ``rollout_eval(params, x0, targets, noise, graph) -> (loss, preds)``
    with preds [B, K, R, N_pad, F]; ``rollout_grad`` additionally returns
    the pmean'd parameter gradients (same contract as
    ``make_gnn_step_fns``'s grad_step).  ``rollout_steps`` must match the
    K dim of ``targets``.
    """
    del cfg  # architecture is entirely encoded in the params pytree
    all_axes = tuple(data_axes) + (graph_axis,)

    def rollout_local(params, x0, targets, noise, graph):
        # x0/noise [B_local, 1, N_pad, F]; targets [B_local, K, 1, N_pad, F]
        g = graph.rank_local()
        tgt = jnp.moveaxis(targets[:, :, 0], 1, 0)        # [K, B, N_pad, F]
        loss, preds = rollout_step(params, x0[:, 0], tgt, g, plan,
                                   noise=noise[:, 0],
                                   axis_names=(graph_axis,))
        if data_axes:
            with jax.named_scope("loss"):
                loss = jax.lax.pmean(loss, tuple(data_axes))
        # preds [K, B, N_pad, F] -> [B, K, 1, N_pad, F]
        return loss, jnp.moveaxis(preds, 0, 1)[:, :, None]

    def grad_local(params, x0, targets, noise, graph):
        (loss, _), grads = jax.value_and_grad(rollout_local, has_aux=True)(
            params, x0, targets, noise, graph)
        with jax.named_scope("grad_sync"):
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, all_axes), grads)
        return loss, grads

    feat = P(tuple(data_axes), graph_axis, None, None)
    seq = P(tuple(data_axes), None, graph_axis, None, None)

    def _wrap(fn, out_specs):
        def call(params, x0, targets, noise, graph):
            graph = as_graph(graph)
            if targets.shape[1] != rollout_steps:
                raise ValueError(
                    f"targets carry K={targets.shape[1]} steps but the step "
                    f"fns were built for rollout_steps={rollout_steps}")
            in_specs = (P(), feat, seq, feat, graph.specs(graph_axis))
            return jax.shard_map(
                fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False,
            )(params, x0, targets, noise, graph)
        return jax.jit(call)

    rollout_eval = _wrap(rollout_local, (P(), seq))
    rollout_grad = _wrap(grad_local, (P(), P()))
    return rollout_eval, rollout_grad


def make_rollout_predict_fn(
    mesh: Mesh,
    cfg: GNNConfig,
    plan: NMPPlan,
    rollout_steps: int,
    data_axes: Sequence[str] = ("data",),
    graph_axis: str = "graph",
):
    """Inference-only wrapper over :func:`make_rollout_step_fns`' eval step.

    ``predict(params, x0, graph) -> preds [B, K, R, N_pad, F]``.

    The scan body consumes ``targets`` only to compute per-step losses —
    predictions depend on ``x0`` and ``params`` alone — so feeding zero
    targets (and zero pushforward noise) through the EXACT jitted program
    the rollout consistency suite pins yields inference predictions with no
    reimplemented forward.  That reuse is what makes the serving engine's
    bitwise-vs-offline contract checkable at all: engine and offline eval
    literally run the same compiled rollout.

    ``x0`` may be a host array; it is placed with the step function's input
    sharding, and the zero targets/noise are built once per input shape and
    cached (the engine calls this with one fixed batch-slot shape).
    """
    rollout_eval, _ = make_rollout_step_fns(
        mesh, cfg, plan, rollout_steps, data_axes, graph_axis)
    rollout_eval = obs.program("rollout_predict", rollout_eval)
    feat_sh = NamedSharding(mesh, P(tuple(data_axes), graph_axis, None, None))
    seq_sh = NamedSharding(mesh, P(tuple(data_axes), None, graph_axis,
                                   None, None))
    zeros_cache: dict = {}

    def predict(params, x0, graph):
        xs = jax.device_put(jnp.asarray(x0, jnp.float32), feat_sh)
        key = tuple(xs.shape)
        if key not in zeros_cache:
            b, r, n, f = xs.shape
            zeros_cache[key] = (
                jax.device_put(
                    jnp.zeros((b, rollout_steps, r, n, f), xs.dtype), seq_sh),
                jax.device_put(jnp.zeros(xs.shape, xs.dtype), feat_sh))
        targets, noise = zeros_cache[key]
        _, preds = rollout_eval(params, xs, targets, noise, graph)
        return preds

    return predict


def make_tgv_rollout_batch_fn(pg: PartitionedGraphs, mesh_sem: SEMMesh,
                              batch: int, rollout_steps: int,
                              dt: float = 0.05, noise_scale=0.0,
                              seed: int = 0):
    """Deterministic Taylor-Green rollout batches keyed by step (replayable).

    Returns ``batch_fn(step) -> (x0, targets, noise)`` with targets the next
    ``rollout_steps`` snapshots of the analytic TGV trajectory.  Pushforward
    noise is drawn on the GLOBAL node field (then gathered per rank), so
    coincident copies receive identical perturbations — a per-copy draw
    would break the 1-rank == R-rank guarantee by construction.

    ``noise_scale`` is a float or a ``step -> float`` callable (annealing
    schedules, see ``TrainConfig.pushforward_noise_final``).
    """
    def batch_fn(step: int):
        scale = noise_scale(step) if callable(noise_scale) else noise_scale
        x0s, tgts, noises = [], [], []
        for b in range(batch):
            t = (step * batch + b) * dt % 2.0
            x0s.append(gather_node_features(
                pg, taylor_green_velocity(mesh_sem.coords, t=t)))
            tgts.append(np.stack([
                gather_node_features(
                    pg, taylor_green_velocity(mesh_sem.coords,
                                              t=t + (k + 1) * dt))
                for k in range(rollout_steps)]))
            rng = np.random.default_rng(
                np.uint64(seed) + np.uint64(step * batch + b))
            nz = rng.normal(size=(mesh_sem.coords.shape[0],
                                  x0s[-1].shape[-1])).astype(np.float32)
            noises.append(scale * gather_node_features(pg, nz))
        return (np.stack(x0s), np.stack(tgts),
                np.stack(noises).astype(np.float32))
    return batch_fn
