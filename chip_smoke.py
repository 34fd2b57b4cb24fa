#!/usr/bin/env python3
"""Chip smoke test: the consistent mesh GNN's main path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # R=4 vs R=1 across four chips only

One process does everything on the chip, each phase prints one line, and
any failure exits non-zero. With one chip:

1. device: platform, kind and count; no TPU, no run.
2. train: ``repro.launch.train`` trains ``GNNConfig.large()`` on
   ``box_mesh((8, 8, 8), p=8)`` (274,625 nodes) at R=1, batch 1, XLA
   backend, blocking, fp32 for 5 steps and writes a checkpoint.
3. serve: ``InferenceEngine`` restores that checkpoint and answers 4
   requests, compared with its offline eval.
4. consistency and kernels on ``box_mesh((4, 4, 4), p=4)`` (4,913 nodes),
   one phase each: the R=1 shard_map step against the stacked R=4
   emulation; the compiled fused Pallas backend against XLA under both
   schedules; the packed halo exchange against the dense one (bitwise),
   and through the whole model. Tolerances are those of the matching tests.

``--chips 4`` runs only R=4 (2x2x1 ranks, about 69k nodes per chip) with the
neighbor and the a2a halo, two steps each, against R=1 on one of the chips.

The last line of standard output is ``{"ok": true, "device": {...}}``.
Timings are observations of this run, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import shutil
import statistics
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
CKPT_DIR = ROOT / "chiprun_out" / "smoke_ckpt"

TRAIN_ELEMENTS, TRAIN_ORDER = (8, 8, 8), 8      # 274,625 nodes
SMALL_ELEMENTS, SMALL_ORDER = (4, 4, 4), 4      # 4,913 nodes
TRAIN_STEPS = 5
N_REQUESTS = 4


def phase(name: str, fn, *args):
    """Run one phase; print its line, or its traceback and exit 1."""
    t0 = time.perf_counter()
    try:
        info = fn(*args)
    except Exception:
        traceback.print_exc()
        print(f"[{name}] FAIL after {time.perf_counter() - t0:.1f}s",
              flush=True)
        sys.exit(1)
    print(f"[{name}] PASS {time.perf_counter() - t0:.1f}s "
          f"{json.dumps(info)}", flush=True)
    return info


def _check(cond: bool, msg: str):
    if not cond:
        raise AssertionError(msg)


def _max_err(a, b) -> float:
    import numpy as np
    return float(np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64)).max())


def _assert_tree_close(got, want, rtol: float, atol: float, what: str) -> float:
    """``assert_allclose`` over two pytrees; returns the max abs error."""
    import jax
    import numpy as np
    err = 0.0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=rtol,
                                   atol=atol, err_msg=what)
        err = max(err, _max_err(g, w))
    return err


def _memory(dev) -> dict:
    stats = dev.memory_stats() or {}
    return {k: stats.get(k) for k in ("peak_bytes_in_use", "bytes_in_use",
                                      "bytes_limit", "largest_alloc_size")}


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def train_phase(dev) -> dict:
    """The training CLI end to end, with a plain (non-retrying) checkpoint."""
    import math

    from repro.ckpt import checkpoint as ckpt
    from repro.launch import train as train_cli

    shutil.rmtree(CKPT_DIR, ignore_errors=True)
    argv = ["--elements", *map(str, TRAIN_ELEMENTS), "--order", str(TRAIN_ORDER),
            "--ranks", "1", "1", "1", "--data-parallel", "1", "--batch", "1",
            "--steps", str(TRAIN_STEPS), "--model", "large", "--halo", "none",
            "--mp-backend", "xla", "--mp-schedule", "blocking",
            "--mp-precision", "fp32", "--ckpt", str(CKPT_DIR)]
    hist = train_cli.main(argv)
    losses = hist["losses"]
    _check(len(losses) == TRAIN_STEPS and all(math.isfinite(v) for v in losses),
           f"losses not {TRAIN_STEPS} finite values: {losses}")
    _check(bool(ckpt.committed_steps(CKPT_DIR)), "no committed checkpoint")
    steps = hist["step_seconds"]
    return {"nodes": math.prod(e * TRAIN_ORDER + 1 for e in TRAIN_ELEMENTS),
            "losses": losses,
            "first_step_s_incl_compile": steps[0],
            "median_step_s_after_warmup": statistics.median(steps[1:]),
            "memory": _memory(dev),
            "checkpoint_steps": ckpt.committed_steps(CKPT_DIR)}


def serve_phase(dev) -> dict:
    """Restore the checkpoint into the engine; 4 requests vs offline eval."""
    import numpy as np

    from repro.core import GNNConfig, box_mesh, taylor_green_velocity
    from repro.runtime.engine import EngineConfig, InferenceEngine

    sem = box_mesh(TRAIN_ELEMENTS, p=TRAIN_ORDER)
    engine = InferenceEngine(CKPT_DIR, GNNConfig.large(),
                             EngineConfig(batch_slots=2, rollout_steps=1))
    mesh_hash = engine.register_mesh(sem)
    engine.warmup()
    snaps = [taylor_green_velocity(sem.coords, t=0.05 * i).astype(np.float32)
             for i in range(N_REQUESTS)]
    with engine:
        futs = [engine.submit(mesh_hash, x, step=i, timeout=600)
                for i, x in enumerate(snaps)]
        results = [f.result(timeout=900) for f in futs]
        refs = [engine.offline_reference(mesh_hash, x) for x in snaps]
    bitwise = all(np.array_equal(r.preds, ref) for r, ref in zip(results, refs))
    err = max(_max_err(r.preds, ref) for r, ref in zip(results, refs))
    scale = max(float(np.abs(ref).max()) for ref in refs)
    for r, ref in zip(results, refs):
        _check(np.isfinite(r.preds).all(), "non-finite prediction")
        _check(r.preds.shape == (1, sem.n_nodes, 3),
               f"prediction shape {r.preds.shape}")
        # the engine's contract is bitwise; fp32 rounding is the floor
        np.testing.assert_allclose(r.preds, ref, rtol=1e-5, atol=1e-6 * scale)
    return {"requests": len(results), "bitwise_vs_offline": bitwise,
            "max_abs_err": err, "ckpt_step": engine.ckpt_step,
            "batches": engine.stats["batches"], "memory": _memory(dev)}


def _small_case(seed: int):
    import jax

    from repro.core import GNNConfig, box_mesh, init_gnn, taylor_green_velocity
    sem = box_mesh(SMALL_ELEMENTS, p=SMALL_ORDER)
    cfg = GNNConfig.large()
    params = init_gnn(jax.random.PRNGKey(seed), cfg)
    return sem, cfg, params, taylor_green_velocity(sem.coords)


def _stacked(params, x, graph, plan, fy):
    """jit-compiled stacked reference: (loss, y, grads)."""
    import jax

    from repro.core.reference import loss_and_grad_stacked
    fn = jax.jit(loss_and_grad_stacked, static_argnums=(4, 5))
    return fn(params, x, x, graph, plan, fy)


def shard_map_vs_stacked(seed: int) -> dict:
    """R=1 production shard_map step == stacked R=4 emulation (Eqs. 2-3)."""
    import jax.numpy as jnp

    from repro.core import (A2A, HaloSpec, NMPPlan, ShardedGraph,
                            gather_node_features, partition_mesh)
    from repro.core.distributed import make_gnn_step_fns, shard_graph
    from repro.launch.mesh import make_mesh

    sem, cfg, params, x_global = _small_case(seed)
    pg1 = partition_mesh(sem, (1, 1, 1))
    plan1 = NMPPlan.build(pg1, "none", axis="graph")
    mesh1 = make_mesh((1, 1), ("data", "graph"))
    _, _, grad_step, _ = make_gnn_step_fns(mesh1, cfg, plan1)
    x1 = jnp.asarray(gather_node_features(pg1, x_global))[None]
    gs1 = shard_graph(mesh1, ShardedGraph.build(pg1, sem.coords, plan1))
    l1, g1 = grad_step(params, x1, x1, gs1)

    pg4 = partition_mesh(sem, (2, 2, 1))
    plan4 = NMPPlan(halo=HaloSpec(mode=A2A))
    graph4 = ShardedGraph.build(pg4, sem.coords, plan4)
    x4 = jnp.asarray(gather_node_features(pg4, x_global))
    l4, _, g4 = _stacked(params, x4, graph4, plan4, cfg.node_out)
    # tests/test_consistency.py: Eq. 2 loss (1e-6), Eq. 3 grads
    d_loss = abs(float(l4) - float(l1))
    _check(d_loss < 1e-6, f"loss R=1 {float(l1)} vs R=4 {float(l4)}")
    err = _assert_tree_close(g4, g1, 1e-3, 2e-6, "grads R=4 vs R=1")
    return {"nodes": sem.n_nodes, "loss": float(l1), "loss_abs_err": d_loss,
            "grad_max_abs_err": err}


def fused_vs_xla(seed: int) -> dict:
    """Compiled fused Pallas backend == XLA backend, values and gradients,
    R=1 and R=4 under the blocking schedule and R=4 under overlap."""
    import jax.numpy as jnp

    from repro.core import (A2A, NONE, HaloSpec, NMPPlan, ShardedGraph,
                            gather_node_features, partition_mesh)

    sem, cfg, params, x_global = _small_case(seed)
    out = {}
    for grid, mode, schedule in (((1, 1, 1), NONE, "blocking"),
                                 ((2, 2, 1), A2A, "blocking"),
                                 ((2, 2, 1), A2A, "overlap")):
        pg = partition_mesh(sem, grid)
        plan_f = NMPPlan(halo=HaloSpec(mode=mode), backend="fused",
                         schedule=schedule).autotune_blocks(cfg.hidden)
        plan_x = plan_f.replace(backend="xla")
        graph = ShardedGraph.build(pg, sem.coords, plan_f)
        x = jnp.asarray(gather_node_features(pg, x_global))
        l_x, y_x, g_x = _stacked(params, x, graph, plan_x, cfg.node_out)
        l_f, y_f, g_f = _stacked(params, x, graph, plan_f, cfg.node_out)
        # tests/test_consistency.py::test_fused_backend_matches_xla_values_and_grads
        d_loss = abs(float(l_f) - float(l_x))
        _check(d_loss < 1e-6 * max(1.0, abs(float(l_x))),
               f"{grid} {schedule}: loss fused {float(l_f)} vs xla {float(l_x)}")
        y_err = _assert_tree_close(y_f, y_x, 1e-4, 1e-5, "y fused vs xla")
        g_err = _assert_tree_close(g_f, g_x, 1e-3, 2e-5, "grads fused vs xla")
        out[f"R{pg.R}-{schedule}"] = {
            "nodes_per_rank": int(pg.n_pad), "edge_tiles": int(
                graph["seg_perm"].shape[1]), "block_e": plan_f.block_e,
            "loss_abs_err": d_loss, "y_max_abs_err": y_err,
            "grad_max_abs_err": g_err}
    return out


def packed_vs_dense(seed: int) -> dict:
    """Packed halo wire (Pallas pack/unpack) vs the dense exchange.

    The exchange alone is pure data movement and must be bitwise equal,
    values and gradients (tests/test_halo_pack.py::
    test_packed_neighbor_bitwise_values_and_grads). Through the whole
    model, packed and dense are two programs whose reductions the compiler
    may order differently, so they are held to the fused-vs-XLA tolerances
    and whether they are bitwise is reported."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import (NEIGHBOR, NMPPlan, ShardedGraph,
                            gather_node_features, partition_mesh)
    from repro.core.halo import halo_sync_stacked
    from repro.core.reference import gnn_forward_stacked

    sem, cfg, params, x_global = _small_case(seed)
    pg = partition_mesh(sem, (2, 2, 1))
    x = jnp.asarray(gather_node_features(pg, x_global))
    out = {}

    plan_p = NMPPlan.build(pg, NEIGHBOR, packed=True)
    graph = ShardedGraph.build(pg, sem.coords, plan_p)
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.normal(size=(pg.R, pg.n_pad, cfg.hidden)),
                    jnp.float32) * jnp.asarray(pg.node_mask)[..., None]
    w = jnp.asarray(rng.normal(size=a.shape), jnp.float32)

    def sync_and_grad(v, graph, spec):
        return jax.value_and_grad(
            lambda u: (halo_sync_stacked(u, graph, spec) * w).sum())(v), \
            halo_sync_stacked(v, graph, spec)

    run = jax.jit(sync_and_grad, static_argnums=(2,))
    (_, g_p), y_p = run(a, graph, plan_p.halo)
    (_, g_d), y_d = run(a, graph, dataclasses.replace(plan_p.halo,
                                                       packed=False))
    _check(_max_err(y_d, a) > 0, "the exchange changed nothing")
    err = max(_max_err(y_p, y_d), _max_err(g_p, g_d))
    _check(err == 0.0, f"exchange: packed vs dense differ by {err}")
    out["exchange_max_abs_err"] = err

    for schedule in ("blocking", "overlap"):
        plan_p = NMPPlan.build(pg, NEIGHBOR, packed=True, schedule=schedule)
        plan_d = NMPPlan.build(pg, NEIGHBOR, packed=False, schedule=schedule)
        graph = ShardedGraph.build(pg, sem.coords, plan_p)

        def fwd_and_grad(p, x, graph, plan):
            def f(q):
                y = gnn_forward_stacked(q, x, graph, plan,
                                        sync_fn=halo_sync_stacked)
                return (y ** 2).sum(), y
            (_, y), g = jax.value_and_grad(f, has_aux=True)(p)
            return y, g

        run = jax.jit(fwd_and_grad, static_argnums=(3,))
        y_d, g_d = run(params, x, graph, plan_d)
        y_p, g_p = run(params, x, graph, plan_p)
        y_err = _assert_tree_close(y_p, y_d, 1e-4, 1e-5, "y packed vs dense")
        g_err = _assert_tree_close(g_p, g_d, 1e-3, 2e-5,
                                   "grads packed vs dense")
        g_scale = max(float(np.abs(np.asarray(g)).max())
                      for g in jax.tree.leaves(g_d))
        out[schedule] = {"y_max_abs_err": y_err, "grad_max_abs_err": g_err,
                         "grad_max_abs": g_scale,
                         "bitwise": y_err == 0.0 and g_err == 0.0}
    return out


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def _two_steps(mesh_dev, pg, sem, cfg, halo: str, seed: int):
    """Two steps of the training loop's own closures: losses, the first
    step's gradients, and the placed graph."""
    import jax

    from repro.core import NMPPlan, init_gnn
    from repro.train.loop import TrainConfig, build_execution
    from repro.train.optimizer import init_adamw

    tcfg = TrainConfig(n_steps=2, batch=1, lr=2e-3, halo_mode=halo,
                       seed=seed, plan=NMPPlan())
    ex = build_execution(mesh_dev, pg, sem, cfg, tcfg, None)
    params = init_gnn(jax.random.PRNGKey(seed), cfg)
    opt = init_adamw(params, ex.opt_cfg)
    losses, first_grads = [], None
    for step in range(2):
        loss, grads = ex.grad_for_step(params, step)
        if first_grads is None:
            first_grads = jax.device_get(grads)
        params, opt, _ = ex.update(params, opt, loss, grads)
        losses.append(float(loss))
    return losses, first_grads, ex.gs, ex.opt_cfg


def _first_adamw_direction(grads, opt_cfg):
    """Per-parameter direction of AdamW's first step (bias-corrected moments
    give ``g / (|g| + eps)`` after global-norm clipping)."""
    import numpy as np
    leaves = [np.asarray(g, np.float64) for g in grads]
    norm = np.sqrt(sum(float((g ** 2).sum()) for g in leaves))
    scale = min(1.0, opt_cfg.clip_norm / max(norm, 1e-12)) \
        if opt_cfg.clip_norm is not None else 1.0
    return [g * scale / (np.abs(g * scale) + opt_cfg.eps) for g in leaves]


def four_chip_phase(seed: int) -> dict:
    """R=4 on four chips (neighbor and a2a halo) vs R=1 on one of them."""
    import math

    import jax
    import numpy as np

    from repro.core import GNNConfig, box_mesh, partition_mesh
    from repro.launch.mesh import make_mesh

    devs = jax.devices()
    sem = box_mesh(TRAIN_ELEMENTS, p=TRAIN_ORDER)
    cfg = GNNConfig.large()
    l1, g1, _, opt_cfg = _two_steps(make_mesh((1, 1), ("data", "graph")),
                                    partition_mesh(sem, (1, 1, 1)), sem, cfg,
                                    "none", seed)
    lr = float(opt_cfg.schedule(1))
    g1_leaves = [np.asarray(g, np.float64) for g in jax.tree.leaves(g1)]
    g1_norm = math.sqrt(sum(float((g ** 2).sum()) for g in g1_leaves))
    u1 = _first_adamw_direction(g1_leaves, opt_cfg)
    pg4 = partition_mesh(sem, (2, 2, 1))
    mesh4 = make_mesh((1, 4), ("data", "graph"))
    out = {"nodes": sem.n_nodes, "nodes_per_chip": int(pg4.n_pad),
           "R1_losses": l1}
    for halo in ("neighbor", "a2a"):
        l4, g4, gs, _ = _two_steps(mesh4, pg4, sem, cfg, halo, seed)
        on = {s.device for leaf in jax.tree.leaves(gs)
              for s in leaf.addressable_shards}
        _check(on == set(devs), f"graph shards on {sorted(d.id for d in on)}")
        # before the optimizer: the loss as the consistency property test
        # (2e-6 relative); gradients by relative norm, as test_rollout
        # compares gradients whose sums run over many terms
        d0 = abs(l4[0] - l1[0])
        _check(d0 < 2e-6 * max(1.0, abs(l1[0])),
               f"{halo}: step-1 loss R=4 {l4[0]} vs R=1 {l1[0]}")
        diff = math.sqrt(sum(float(((np.asarray(a, np.float64) - b) ** 2).sum())
                             for a, b in zip(jax.tree.leaves(g4), g1_leaves)))
        _check(diff / g1_norm < 5e-4, f"{halo}: grad rel err {diff / g1_norm}")
        # AdamW's first step moves each parameter by lr * g/(|g| + eps): a
        # near-zero gradient whose sign differs between R=4 and R=1 moves
        # its parameter up to 2*lr apart. The step-2 losses may then differ
        # by the first-order change sum |g| * lr * |u4 - u1| (doubled for
        # the second-order term) plus the loss's own rounding
        u4 = _first_adamw_direction(jax.tree.leaves(g4), opt_cfg)
        bound = 2e-6 * max(1.0, abs(l1[1])) + 2 * lr * sum(
            float((np.abs(g) * np.abs(a - b)).sum())
            for g, a, b in zip(g1_leaves, u4, u1))
        d1 = abs(l4[1] - l1[1])
        _check(d1 <= bound, f"{halo}: step-2 loss R=4 {l4[1]} vs R=1 "
                            f"{l1[1]} (bound {bound})")
        out[halo] = {"R4_losses": l4, "step1_loss_abs_err": d0,
                     "grad_rel_err": diff / g1_norm, "step2_loss_abs_err": d1,
                     "step2_bound": bound}
    out["memory"] = {str(d.id): _memory(d) for d in devs}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the R=4 vs R=1 cross-chip comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"{ROOT / 'src' / 'repro'} not found: run chip_smoke.py from a "
              "checkout of the repository", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    devs = jax.devices()
    dev = devs[0]
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}", flush=True)
    if dev.platform != "tpu":
        print(f"no TPU found (JAX platform {dev.platform!r})", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} chips; JAX sees "
              f"{len(devs)}", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache
    print(f"[cache] {enable_compile_cache()}", flush=True)
    if args.chips == 4:
        phase("four-chip", four_chip_phase, args.seed)
    else:
        try:
            phase("train", train_phase, dev)
            phase("serve", serve_phase, dev)
        finally:
            shutil.rmtree(CKPT_DIR, ignore_errors=True)
        phase("shard-map-vs-stacked", shard_map_vs_stacked, args.seed)
        phase("fused-vs-xla", fused_vs_xla, args.seed)
        phase("packed-vs-dense", packed_vs_dense, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
