"""Production training loop for the consistent distributed GNN.

Combines: the shard_map grad step (real halo collectives), AdamW, async
checkpointing, fault-tolerant restart, straggler monitoring, and the
consistent loss. Used by examples/train_cfd_gnn.py and the training-
consistency benchmark.

Two training modes, selected by ``TrainConfig.rollout_steps``:

* 1 (default) — one-step prediction (the paper's Fig. 6 training);
* K > 1       — autoregressive rollout training (``repro.train.rollout``):
  the model is scanned over its own predictions for K steps, every step's
  halo-consistent loss enters the objective, and
  ``TrainConfig.pushforward_noise`` optionally perturbs the initial state
  (stop-gradient pushforward trick) to emulate inference-time drift.

Execution policy (backend/schedule/precision/...) is a single
:class:`~repro.core.graph_state.NMPPlan` on the TrainConfig; the per-level
halo specs are filled in from the partition at launch.

Elastic fault tolerance (``TrainConfig.resilience``): the loop is driven by
``repro.runtime.fault_tolerance.run_resilient`` — periodic + straggler-
triggered async checkpoints whose manifests carry a *mesh fingerprint*
(mesh hash, rank count, partitioner, plan policy, replay-critical training
config) and the loss-history tail, catch-all crash recovery with bounded
exponential backoff, and :func:`resume_elastic` restore.  Because the
paper's consistency guarantee makes the partition arithmetically invisible
(Eq. 2/3), a checkpoint written on R ranks restores onto R' ranks — or a
different partitioner — and the loss trajectory *continues*: bitwise when
the partition is unchanged, to float32 summation tolerance (~1e-7 relative)
across a repartition.  Batches are replayed deterministically: every batch
function is pure in ``step`` (see CONTRIBUTING.md "Elastic resume").
"""
from __future__ import annotations

import dataclasses
import hashlib
import time
from types import SimpleNamespace
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.distributed import make_gnn_step_fns, shard_graph
from repro.core.gnn import GNNConfig, init_gnn
from repro.core.graph_state import AUTO, BLOCKING, OVERLAP, NMPPlan, ShardedGraph
from repro.core.mesh_gen import SEMMesh, taylor_green_velocity
from repro.core.partition import PartitionedGraphs, gather_node_features
from repro.ckpt import checkpoint as ckpt
from repro.runtime.fault_tolerance import (
    FaultPlan, ResilientConfig, run_resilient,
)
from repro.runtime.straggler import StragglerMonitor
from repro.train.optimizer import AdamWConfig, adamw_update, init_adamw
from repro.train.rollout import (
    curriculum_k, make_rollout_step_fns, make_tgv_rollout_batch_fn,
)


@dataclasses.dataclass
class TrainConfig:
    n_steps: int = 200
    batch: int = 1
    lr: float = 1e-3
    halo_mode: str = "neighbor"
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 100
    log_every: int = 20
    seed: int = 0
    # NMP execution policy (halo specs are filled in from the partition by
    # train_consistent_gnn; schedule="auto" is resolved against the built
    # graph via NMPPlan.autotune); see repro.core.graph_state.NMPPlan
    plan: NMPPlan = NMPPlan()
    # --- autoregressive rollout training (repro.train.rollout) ---
    rollout_steps: int = 1       # K > 1 scans the model over its predictions
    pushforward_noise: float = 0.0  # stddev of the stop-grad step-1 noise
    # curriculum: per-stage K values, e.g. (1, 2, 4) splits n_steps into
    # three even stages of increasing rollout depth (overrides
    # rollout_steps); step fns are built once per distinct K
    rollout_curriculum: tuple = ()
    # anneal pushforward noise linearly from pushforward_noise to this
    # value over the run (None = constant)
    pushforward_noise_final: Optional[float] = None
    # which mesh decomposition produced ``pg`` ("block" | "spectral") —
    # recorded in the checkpoint fingerprint so an elastic resume knows
    # whether the partitioner changed (allowed: results are consistent)
    partitioner: str = "block"
    # elastic fault tolerance: not None switches the loop to the
    # run_resilient driver (auto-resume from ckpt_dir, crash recovery with
    # bounded backoff, fingerprinted manifests). ``ckpt_dir``/``ckpt_every``
    # above are the plain fire-and-forget checkpoint knobs and are ignored
    # when resilience is configured.
    resilience: Optional[ResilientConfig] = None


def make_tgv_batch_fn(pg: PartitionedGraphs, mesh_sem: SEMMesh, batch: int,
                      dt: float = 0.05):
    """Deterministic Taylor-Green snapshot batches keyed by step (replayable)."""
    def batch_fn(step: int):
        xs = []
        for b in range(batch):
            t = (step * batch + b) * dt % 2.0
            xs.append(gather_node_features(pg, taylor_green_velocity(mesh_sem.coords, t=t)))
        x = np.stack(xs)             # [B, R, N_pad, F] — autoencoding target = input
        return x
    return batch_fn


def mesh_fingerprint_hash(sem_mesh: SEMMesh) -> str:
    """Content hash of the global mesh (node coords + element connectivity).
    Partition-independent: every rank count / partitioner of the same mesh
    hashes identically, so it is the checkpoint field that rejects resuming
    onto a *different problem* while allowing elastic repartitioning."""
    h = hashlib.sha256()
    h.update(np.ascontiguousarray(sem_mesh.coords).tobytes())
    h.update(np.ascontiguousarray(sem_mesh.elem_nodes).tobytes())
    return h.hexdigest()[:16]


# fingerprint fields that MUST match between save and resume: they define
# the trajectory (problem + deterministic batch replay + optimizer math).
# Everything else (ranks, partitioner, halo_mode, policy) is execution
# layout — arithmetically invisible under the consistency guarantee.
_REPLAY_FIELDS = ("mesh_hash", "n_global", "seed", "batch", "lr",
                  "rollout_steps", "rollout_curriculum", "pushforward_noise",
                  "pushforward_noise_final", "n_levels", "hidden")


def run_fingerprint(sem_mesh: SEMMesh, pg: PartitionedGraphs, cfg: GNNConfig,
                    tcfg: TrainConfig, plan: NMPPlan) -> dict:
    """The manifest ``extra["fingerprint"]`` a checkpoint carries."""
    return {
        "mesh_hash": mesh_fingerprint_hash(sem_mesh),
        "n_global": int(pg.n_global),
        "ranks": int(pg.R),
        "partitioner": tcfg.partitioner,
        "halo_mode": tcfg.halo_mode,
        "policy": plan.policy(),
        "seed": int(tcfg.seed),
        "batch": int(tcfg.batch),
        "lr": float(tcfg.lr),
        "rollout_steps": int(tcfg.rollout_steps),
        "rollout_curriculum": list(tcfg.rollout_curriculum),
        "pushforward_noise": float(tcfg.pushforward_noise),
        "pushforward_noise_final": tcfg.pushforward_noise_final,
        "n_levels": int(cfg.n_levels),
        "hidden": int(cfg.hidden),
    }


def _init_state(cfg: GNNConfig, tcfg: TrainConfig, opt_cfg: AdamWConfig) -> dict:
    key = jax.random.PRNGKey(tcfg.seed)
    params = init_gnn(key, cfg)
    return {"params": params, "opt": init_adamw(params, opt_cfg), "rng": key}


def build_execution(mesh_dev, pg, sem_mesh, cfg, tcfg, hierarchy):
    """Build everything a training step needs for the CURRENT partition:
    plan (halo specs + resolved schedule), ShardedGraph, sharded placement,
    and the per-step grad/update closures.  Shared by the plain and the
    resilient paths — an elastic resume simply rebuilds this for the new
    rank grid and restores params/opt into it.  Each step's batch, built on
    the host and placed, is the ``repro.obs`` span ``train/batch`` (id: the
    step); the optimizer step is the program ``update``."""
    if cfg.n_levels > 1 and hierarchy is None:
        raise ValueError("cfg.n_levels > 1 needs hierarchy= "
                         "(repro.core.coarsen.build_hierarchy)")
    # fill the per-level halo specs into the policy plan
    plan = NMPPlan.build(
        hierarchy if hierarchy is not None and cfg.n_levels > 1 else pg,
        tcfg.halo_mode, axis="graph",
        **{f.name: getattr(tcfg.plan, f.name)
           for f in dataclasses.fields(NMPPlan)
           if f.name not in ("halo", "coarse_halos")})
    # layout + interior/boundary split passes are cached on pg — one
    # host-side pass per partition, amortized over every training step
    graph = ShardedGraph.build(
        pg, sem_mesh.coords, plan,
        hierarchy=hierarchy if cfg.n_levels > 1 else None)
    # schedule="auto": on a same-rank-count resume, reuse the schedule the
    # original run measured (recorded in the manifest fingerprint) so the
    # replayed trajectory runs the exact same program; otherwise measure
    # blocking vs overlap on this (graph, R) once and commit to the winner
    ckpt_dir = tcfg.resilience.ckpt_dir if tcfg.resilience else tcfg.ckpt_dir
    if plan.schedule == AUTO and ckpt_dir:
        try:
            manifest = ckpt.peek_manifest(ckpt_dir)
        except ckpt.CheckpointCorruption:
            manifest = None
        fp = (manifest or {}).get("extra", {}).get("fingerprint", {})
        prev = fp.get("policy", {})
        if (fp.get("ranks") == pg.R and prev.get("backend") == plan.backend
                and prev.get("schedule") in (BLOCKING, OVERLAP)):
            plan = plan.replace(schedule=prev["schedule"])
    plan = plan.autotune(graph, hidden=cfg.hidden)

    opt_cfg = AdamWConfig(schedule=lambda s: jnp.asarray(tcfg.lr), weight_decay=0.0)

    @jax.jit
    def update(params, opt_state, loss, grads):
        return adamw_update(grads, opt_state, params, opt_cfg)
    update = obs.program("update", update)

    # the static graph is loop-invariant: place it once, not per step
    gs = shard_graph(mesh_dev, graph)
    feat_sh = NamedSharding(mesh_dev, P(("data",), "graph", None, None))
    stages = tuple(tcfg.rollout_curriculum)
    if stages or tcfg.rollout_steps > 1:
        # rollout path; a curriculum splits n_steps into even stages of
        # increasing K (the 1 -> 2 -> 4 schedule of the pushforward line of
        # work), with step fns / batch fns built once per distinct K
        stages = stages or (tcfg.rollout_steps,)
        noise_scale = tcfg.pushforward_noise
        if tcfg.pushforward_noise_final is not None:
            n0 = tcfg.pushforward_noise
            n1 = tcfg.pushforward_noise_final
            denom = max(tcfg.n_steps - 1, 1)
            noise_scale = lambda s: n0 + (n1 - n0) * (s / denom)  # noqa: E731
        seq_sh = NamedSharding(mesh_dev, P(("data",), None, "graph", None, None))
        fns_by_k = {}

        def k_for_step(step: int) -> int:
            return curriculum_k(stages, tcfg.n_steps, step)

        def grad_for_step(params, step):
            k = k_for_step(step)
            if k not in fns_by_k:
                _, rollout_grad = make_rollout_step_fns(mesh_dev, cfg, plan, k)
                bf = make_tgv_rollout_batch_fn(
                    pg, sem_mesh, tcfg.batch, k,
                    noise_scale=noise_scale, seed=tcfg.seed)
                fns_by_k[k] = (rollout_grad, bf)
            rollout_grad, batch_fn = fns_by_k[k]
            with obs.span("train/batch", step):
                x0, targets, noise = batch_fn(step)
                xs = jax.device_put(jnp.asarray(x0), feat_sh)
                ts = jax.device_put(jnp.asarray(targets), seq_sh)
                ns = jax.device_put(jnp.asarray(noise), feat_sh)
            return rollout_grad(params, xs, ts, ns, gs)
    else:
        _, _, grad_step, _ = make_gnn_step_fns(mesh_dev, cfg, plan)
        batch_fn = make_tgv_batch_fn(pg, sem_mesh, tcfg.batch)

        def k_for_step(step: int) -> int:
            return 1

        def grad_for_step(params, step):
            with obs.span("train/batch", step):
                xs = jax.device_put(jnp.asarray(batch_fn(step)), feat_sh)
            return grad_step(params, xs, xs, gs)

    return SimpleNamespace(plan=plan, graph=graph, gs=gs, opt_cfg=opt_cfg,
                           update=update, grad_for_step=grad_for_step,
                           k_for_step=k_for_step)


def resume_elastic(ckpt_dir, mesh_dev, pg, sem_mesh, cfg, tcfg, plan):
    """Elastic restore: latest valid checkpoint onto the CURRENT mesh/partition.

    The caller has already rebuilt ``PartitionedGraphs`` (+ ``ShardedGraph``
    + ``NMPPlan`` via :func:`build_execution`) for the new rank grid —
    block or spectral; this function restores the *portable* state
    (params, opt, rng are partition-independent: replicated over the graph
    axis) onto ``mesh_dev`` via per-leaf shardings, validates the manifest
    fingerprint, and classifies the resume:

      * replay-critical mismatch (different mesh, seed, batch schedule,
        optimizer or model config) → ``ValueError`` naming the field: the
        checkpoint belongs to a different trajectory;
      * execution-layout mismatch (rank count, partitioner, halo mode,
        plan policy) → allowed, returned as the ``elastic`` record — the
        consistency guarantee makes the trajectory continue.

    Returns ``None`` when no committed checkpoint exists, else
    ``(state, start_step, prior_losses, manifest, elastic_or_None)``.
    Corrupted newest checkpoints fall back to the previous committed step
    (``ckpt.restore_with_fallback``).
    """
    if not ckpt.committed_steps(ckpt_dir):
        return None
    opt_cfg = AdamWConfig(schedule=lambda s: jnp.asarray(tcfg.lr), weight_decay=0.0)
    template = _init_state(cfg, tcfg, opt_cfg)
    replicated = NamedSharding(mesh_dev, P())
    shardings = jax.tree.map(lambda _: replicated, template)
    state, manifest = ckpt.restore_with_fallback(ckpt_dir, template,
                                                 shardings=shardings)
    fp_now = run_fingerprint(sem_mesh, pg, cfg, tcfg, plan)
    fp_old = manifest.get("extra", {}).get("fingerprint")
    elastic = None
    if fp_old:
        for field in _REPLAY_FIELDS:
            if fp_old.get(field) != fp_now.get(field):
                raise ValueError(
                    f"cannot resume from {ckpt_dir}: replay-critical "
                    f"fingerprint field {field!r} changed "
                    f"({fp_old.get(field)!r} -> {fp_now.get(field)!r}) — "
                    "this checkpoint belongs to a different trajectory")
        changed = {k: [fp_old.get(k), fp_now.get(k)]
                   for k in ("ranks", "partitioner", "halo_mode", "policy")
                   if fp_old.get(k) != fp_now.get(k)}
        if changed:
            elastic = {"step": manifest["step"] + 1,
                       "from_ranks": fp_old.get("ranks"),
                       "to_ranks": fp_now.get("ranks"),
                       "from_partitioner": fp_old.get("partitioner"),
                       "to_partitioner": fp_now.get("partitioner"),
                       "changed": changed}
    start = manifest["step"] + 1
    extra = manifest.get("extra", {})
    off = int(extra.get("losses_offset", 0))
    losses = list(extra.get("losses", []))[:max(start - off, 0)]
    return state, start, losses, manifest, elastic


def _train_resilient(ex, mesh_dev, pg, sem_mesh, cfg, tcfg,
                     fault: Optional[FaultPlan]) -> dict:
    rcfg = tcfg.resilience
    fp = run_fingerprint(sem_mesh, pg, cfg, tcfg, ex.plan)
    monitor = StragglerMonitor()
    elastic_events = []

    def init_state_fn():
        return _init_state(cfg, tcfg, ex.opt_cfg)

    def step_fn(state, step):
        loss, grads = ex.grad_for_step(state["params"], step)
        params, opt_state, _ = ex.update(state["params"], state["opt"],
                                         loss, grads)
        return ({"params": params, "opt": opt_state, "rng": state["rng"]},
                {"loss": float(loss)})

    def restore_fn():
        res = resume_elastic(rcfg.ckpt_dir, mesh_dev, pg, sem_mesh, cfg,
                             tcfg, ex.plan)
        if res is None:
            return None
        state, start, losses, manifest, elastic = res
        if elastic is not None:
            elastic_events.append(elastic)
            # the per-step time scale changed with the layout — stale EWMA
            # stats would flag the first steps as stragglers
            monitor.reset()
        return state, start, losses

    state, history = run_resilient(
        init_state_fn, step_fn, lambda step: step, tcfg.n_steps, rcfg,
        monitor=monitor, fault=fault, restore_fn=restore_fn,
        manifest_extra={"fingerprint": fp})
    history["rollout_k"] = [ex.k_for_step(s) for s in range(tcfg.n_steps)]
    history["schedule"] = ex.plan.schedule
    history["elastic"] = elastic_events[-1] if elastic_events else None
    history["params"] = state["params"]
    return history


def train_consistent_gnn(
    mesh_dev,
    pg: PartitionedGraphs,
    sem_mesh: SEMMesh,
    cfg: GNNConfig,
    tcfg: TrainConfig,
    hierarchy=None,
    fault: Optional[FaultPlan] = None,
) -> dict:
    """Full training run; returns history with losses (paper Fig. 6 right).

    ``hierarchy`` (``repro.core.coarsen.MultiLevelGraphs`` with ``pg`` as
    level 0) enables the consistent multilevel V-cycle when
    ``cfg.n_levels > 1``: each coarse level gets its own halo spec and its
    static arrays ride along as nested ShardedGraph levels.

    With ``tcfg.resilience`` set, the run is driven by ``run_resilient``:
    it auto-resumes from the newest valid checkpoint in
    ``resilience.ckpt_dir`` (elastically — the checkpoint may come from a
    different rank count or partitioner), recovers from crashes up to
    ``max_restarts`` with bounded exponential backoff, and checkpoints
    periodically plus on straggler events.  ``fault`` injects failures for
    tests/drivers (see ``FaultPlan``); it is only honored on the resilient
    path.
    """
    ex = build_execution(mesh_dev, pg, sem_mesh, cfg, tcfg, hierarchy)
    if tcfg.resilience is not None:
        return _train_resilient(ex, mesh_dev, pg, sem_mesh, cfg, tcfg, fault)

    fp = run_fingerprint(sem_mesh, pg, cfg, tcfg, ex.plan)
    state = _init_state(cfg, tcfg, ex.opt_cfg)
    params, opt_state = state["params"], state["opt"]
    monitor = StragglerMonitor()
    saver = ckpt.AsyncCheckpointer(tcfg.ckpt_dir) if tcfg.ckpt_dir else None

    history = {"losses": [], "rollout_k": [], "schedule": ex.plan.schedule,
               "step_seconds": []}
    for step in range(tcfg.n_steps):
        t0 = time.perf_counter()
        monitor.start_step()
        loss, grads = ex.grad_for_step(params, step)
        params, opt_state, _ = ex.update(params, opt_state, loss, grads)
        monitor.end_step(step)
        # host clock from dispatch to the loss on the host: step 0 holds the
        # compile; the device runs update(s-1) before grad(s), so each later
        # entry covers one gradient and one update
        history["losses"].append(float(loss))
        history["step_seconds"].append(time.perf_counter() - t0)
        history["rollout_k"].append(ex.k_for_step(step))
        if saver and (step % tcfg.ckpt_every == 0 or step == tcfg.n_steps - 1):
            # same tree + fingerprinted manifest as the resilient path, so
            # a plain run's checkpoints are elastically resumable too
            saver.save(step, {"params": params, "opt": opt_state,
                              "rng": state["rng"]},
                       extra={"reason": "periodic", "fingerprint": fp,
                              "losses": list(history["losses"]),
                              "losses_offset": 0})
    if saver:
        saver.wait()
    history["straggler_events"] = len(monitor.events)
    history["params"] = params
    return history
