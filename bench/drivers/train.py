"""Training traffic: the plain loop of ``repro.train.loop`` at one chip's
share of a partitioned mesh.

Set-up builds the mesh, the partition and the program's execution
(``build_execution``: plan, ``ShardedGraph``, placement, and the
``grad_for_step`` / ``update`` closures), makes the weights and AdamW state
on the device in one jitted call from the seed, and drives that execution
through its first ``check_steps`` steps, which warms every program the
window runs. The window then continues the same loop. Each step is the
loop's own: the step-keyed Taylor-Green batch, ``grad_for_step``,
``update``, and the loss read back to the host. The starting step is drawn
from the seed.

After the window the plain reference (``bench/reference.py``) trains from
the same seed on the same snapshots, and ``bench/compare.py`` compares the
checked steps' losses, the first gradient as AdamW received it (its first
moment over 1 - b1) and the parameters' change. ``calibrate`` gives the
readings the limits are set from (``bench/calibrate.py``).
"""
from __future__ import annotations

import contextlib
import json
import math
import sys
import time
from types import SimpleNamespace

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from bench import compare, flops, harness, reference, trace


def snapshot_times(mix: dict, steps) -> list[float]:
    """The loop's snapshot time of each (step, batch row), in step order."""
    b = mix["batch"]
    return [(s * b + i) * mix["dt"] % mix["t_period"] for s in steps for i in range(b)]


def first_step(mix: dict, seed: int) -> int:
    period = round(mix["t_period"] / mix["dt"])
    return int(np.random.default_rng(seed).integers(period))


def build(conf: dict, mix: dict, devices, seed: int, span) -> SimpleNamespace:
    """The program's execution of the cell's graph, and its step."""
    from repro.core import GNNConfig, box_mesh, init_gnn, partition_mesh
    from repro.train.loop import TrainConfig, build_execution
    from repro.train.optimizer import init_adamw

    mesh_cf = conf["mesh"]
    gcfg = GNNConfig(name=conf["name"], **conf["model"])
    ranks = tuple(mesh_cf["ranks"])
    with span("mesh_gen"):
        sem = box_mesh(tuple(mesh_cf["elements"]), p=mesh_cf["order"])
    with span("partition"):
        pg = partition_mesh(sem, ranks)
    mesh_dev = jax.make_mesh((1, math.prod(ranks)), ("data", "graph"), devices=devices,
                             axis_types=(AxisType.Auto,) * 2)
    tcfg = TrainConfig(batch=mix["batch"], lr=conf["optimizer"]["lr"],
                       halo_mode="none" if math.prod(ranks) == 1 else mix["halo"],
                       seed=seed)
    with span("graph_build"):
        ex = build_execution(mesh_dev, pg, sem, gcfg, tcfg, None)
        jax.block_until_ready(ex.gs)
    print(f"plan: {ex.plan.policy()}", file=sys.stderr)
    init = jax.jit(lambda k: (lambda p: (p, init_adamw(p, ex.opt_cfg)))(init_gnn(k, gcfg)))

    def step(params, opt, s):
        with span("batch+grad_dispatch"):
            loss, grads = ex.grad_for_step(params, s)
        with span("update"):
            params, opt, _ = ex.update(params, opt, loss, grads)
        with span("loss_fetch"):
            value = float(loss)
        return params, opt, value

    return SimpleNamespace(ex=ex, pg=pg, gcfg=gcfg, mesh_dev=mesh_dev, init=init, step=step)


def checked_steps(prog, mix: dict, seed: int, b1: float):
    """Weights from the seed, then the first ``check_steps`` steps of the
    loop. Returns the state after them and, for the comparison, the losses,
    the first gradient as AdamW received it, and the parameters' change."""
    params, opt = prog.init(jax.random.PRNGKey(seed))
    p0, losses = params, []
    s0 = first_step(mix, seed)
    for i in range(mix["check_steps"]):
        params, opt, value = prog.step(params, opt, s0 + i)
        losses.append(value)
        if i == 0:
            grad1 = jax.tree.map(lambda m: np.asarray(m) / (1 - b1), opt["m"])
    change = compare.tree_sub(jax.device_get(params), jax.device_get(p0))
    return params, opt, (losses, grad1, change)


def reference_steps(conf: dict, mix: dict, seed: int, graph: reference.Graph,
                    precision=reference.HIGHEST, loss_fn=reference.loss):
    """The reference's losses, first clipped gradient and parameters' change
    over the same steps from the same seed."""
    s0 = first_step(mix, seed)
    xs = [reference.taylor_green(graph.coords, t, mix["nu"])
          for t in snapshot_times(mix, range(s0, s0 + mix["check_steps"]))]
    xs = [jnp.asarray(np.stack(xs[i:i + mix["batch"]]))
          for i in range(0, len(xs), mix["batch"])]
    r0 = jax.jit(lambda k: reference.init_params(k, conf["model"]))(
        jax.random.PRNGKey(seed))
    losses, grad1, r1 = reference.train(r0, xs, graph.arrays, conf["optimizer"],
                                        precision, loss_fn)
    return losses, grad1, compare.tree_sub(r1, r0)


def program_memory(prog, params, opt, mix: dict, node_in: int) -> int | None:
    """The larger footprint of the two programs the window runs, by the
    compiler: the gradient step as ``make_gnn_step_fns`` builds it for the
    execution's plan, and the execution's jitted ``update``."""
    from repro.core.distributed import make_gnn_step_fns

    pg = prog.pg
    feat = jax.ShapeDtypeStruct((mix["batch"], pg.R, pg.n_pad, node_in), jnp.float32,
                                sharding=NamedSharding(prog.mesh_dev, P(("data",), "graph")))
    loss = jax.ShapeDtypeStruct((), jnp.float32)

    def grad_step():
        step = make_gnn_step_fns(prog.mesh_dev, prog.gcfg, prog.ex.plan)[2]
        return step.lower(params, feat, feat, prog.ex.gs)
    sizes = [harness.program_bytes("grad_step", grad_step),
             harness.program_bytes("update",
                                   lambda: prog.ex.update.lower(params, opt, loss, params))]
    return max((b for b in sizes if b is not None), default=None)


def run(cell) -> dict:
    conf, mix, rec = cell.config, cell.mix, cell.record
    span = rec.spans
    prog = build(conf, mix, cell.devices, cell.seed, span)
    with span("warmup"):
        params, opt, prog_readings = checked_steps(prog, mix, cell.seed,
                                                   conf["optimizer"]["b1"])
        jax.block_until_ready(params)
    rec.setup_end = time.perf_counter()

    s = first_step(mix, cell.seed) + mix["check_steps"]
    n = failed = 0
    with trace.capture(cell.trace, rec):
        t0 = time.perf_counter()
        with span("window"):
            while time.perf_counter() - t0 < cell.seconds:
                params, opt, value = prog.step(params, opt, s + n)
                n += 1
                failed += not math.isfinite(value)
            jax.block_until_ready((params, opt))
        rec.window = (t0, time.perf_counter())
    rec.units = n
    rec.flops_per_unit = flops.train_step_flops(
        conf["model"], *flops.box_graph_size(conf["mesh"]["elements"], conf["mesh"]["order"]),
        mix["batch"])
    runtime_peak = trace.memory_peak(cell.devices)
    program = program_memory(prog, params, opt, mix, conf["model"]["node_in"])
    del prog, params, opt          # the program's device buffers are freed

    graph = reference.Graph(conf["mesh"]["elements"], conf["mesh"]["order"])
    ref_readings = reference_steps(conf, mix, cell.seed, graph)
    return {"metrics": {"train_step_ms": rec.window_s / n * 1e3},
            "attempted": n, "failed": failed,
            "readings": compare.train_gaps(prog_readings, ref_readings),
            "memory_runtime_peak_bytes": runtime_peak, "memory_program_bytes": program}


def half_batch_loss(params, xs, graph, precision, remat=True):
    """The reference's loss with half of the batch left out: the mean over
    the first half of the nodes (the fault a training cell can have)."""
    ys = jax.vmap(lambda x: reference.forward(params, x, graph, precision, remat))(xs)
    half = xs.shape[1] // 2
    return jnp.mean(jnp.square(ys - xs)[:, :half])


def calibrate(conf: dict, mix: dict, devices, seeds: list[int], n_control: int) -> dict:
    """Readings at the cell's own size, the graph and programs built once:
    ``program`` on every seed, compared as a run compares it;
    ``control_high``, the reference at the chip's ``Precision.HIGH`` in the
    program's place, and ``half_batch``, the reference with half of the
    batch left out, on the first ``n_control`` seeds."""
    prog = build(conf, mix, devices, seeds[0], lambda name: contextlib.nullcontext())
    graph = reference.Graph(conf["mesh"]["elements"], conf["mesh"]["order"])
    out = {"program": [], "control_high": [], "half_batch": []}
    for i, seed in enumerate(seeds):
        _, _, got = checked_steps(prog, mix, seed, conf["optimizer"]["b1"])
        want = reference_steps(conf, mix, seed, graph)
        out["program"].append(dict(seed=seed, **compare.train_gaps(got, want)))
        if i < n_control:
            for name, kw in (("control_high", {"precision": jax.lax.Precision.HIGH}),
                             ("half_batch", {"loss_fn": half_batch_loss})):
                got = reference_steps(conf, mix, seed, graph, **kw)
                out[name].append(dict(seed=seed, **compare.train_gaps(got, want)))
        print(json.dumps({k: v[-1] for k, v in out.items() if v}), flush=True)
    return out
