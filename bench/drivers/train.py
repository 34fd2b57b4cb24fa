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

The mix may name the mesh (``mesh``: elements, order and the rank grid it
is partitioned over); where it names none, the configuration's holds.

After the window the plain reference (the module the configuration names,
``bench/reference.py`` by default) trains from the same seed on the same
snapshots, on the unpartitioned graph, and ``bench/compare.py`` compares
the checked steps' losses, the first gradient as AdamW received it (its
first moment over 1 - b1) and the parameters' change. On a cell of one
chip it runs on JAX's default device; on a cell of more, whose global graph
outgrows one chip, over the cell's chips, its edges split among them and
its nodes and weights on every one (XLA partitions the reference's plain
``jax.numpy``). ``calibrate`` gives the readings the limits are set from
(``bench/calibrate.py``).
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
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from bench import compare, flops, harness, trace


def snapshot_times(mix: dict, steps) -> list[float]:
    """The loop's snapshot time of each (step, batch row), in step order."""
    b = mix["batch"]
    return [(s * b + i) * mix["dt"] % mix["t_period"] for s in steps for i in range(b)]


def first_step(mix: dict, seed: int) -> int:
    period = round(mix["t_period"] / mix["dt"])
    return int(np.random.default_rng(seed).integers(period))


def reference_mesh(devices) -> Mesh | None:
    """Where the plain reference runs: None, JAX's default device, for a
    cell of one chip; for more, a mesh of the cell's ``devices`` whose axis
    ``edges`` splits the reference's edges."""
    return Mesh(np.asarray(devices), ("edges",)) if len(devices) > 1 else None


def build(conf: dict, mix: dict, devices, seed: int, span) -> SimpleNamespace:
    """The program's execution of the cell's graph, and its step."""
    from repro.core import GNNConfig, box_mesh, init_gnn, partition_mesh
    from repro.train.loop import TrainConfig, build_execution
    from repro.train.optimizer import init_adamw

    mesh_cf = harness.mesh_of(conf, mix)
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
    return SimpleNamespace(ex=ex, pg=pg, sem=sem, gcfg=gcfg, tcfg=tcfg, mesh_dev=mesh_dev,
                           init=init, step=stepper(ex, span))


def stepper(ex, span):
    """One step of the loop through the execution ``ex``: the batch and the
    gradient, AdamW's update, and the loss read back to the host."""
    def step(params, opt, s):
        with span("batch+grad_dispatch"):
            loss, grads = ex.grad_for_step(params, s)
        with span("update"):
            params, opt, _ = ex.update(params, opt, loss, grads)
        with span("loss_fetch"):
            value = float(loss)
        return params, opt, value
    return step


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


def reference_graph(conf: dict, mix: dict, devices, ref=None):
    """The reference's unpartitioned graph of the cell's mesh, placed where
    the reference runs (``reference_mesh``)."""
    ref = ref or harness.reference_module(conf)
    mesh_cf = harness.mesh_of(conf, mix)
    graph = ref.Graph(mesh_cf["elements"], mesh_cf["order"])
    mesh = reference_mesh(devices)
    if mesh is not None:
        graph.arrays = {k: jax.device_put(a, NamedSharding(mesh, P("edges")))
                        for k, a in graph.arrays.items()}
    return graph


def reference_steps(conf: dict, mix: dict, seed: int, graph, precision=None,
                    loss_fn=None, ref=None):
    """The reference's losses, first clipped gradient and parameters' change
    over the same steps from the same seed, where ``graph`` was placed; the
    snapshots and weights are on every device of a sharded graph.
    ``precision`` and ``loss_fn`` default to the reference's own."""
    ref = ref or harness.reference_module(conf)
    precision = ref.HIGHEST if precision is None else precision
    sharding = graph.arrays["src"].sharding
    every = NamedSharding(sharding.mesh, P()) if isinstance(sharding, NamedSharding) else None
    s0 = first_step(mix, seed)
    xs = [ref.taylor_green(graph.coords, t, mix["nu"])
          for t in snapshot_times(mix, range(s0, s0 + mix["check_steps"]))]
    xs = [jnp.asarray(np.stack(xs[i:i + mix["batch"]]))
          for i in range(0, len(xs), mix["batch"])]
    if every is not None:
        xs = [jax.device_put(x, every) for x in xs]
    r0 = jax.jit(lambda k: ref.init_params(k, conf["model"]), out_shardings=every)(
        jax.random.PRNGKey(seed))
    losses, grad1, r1 = ref.train(r0, xs, graph.arrays, conf["optimizer"],
                                  precision, loss_fn or ref.loss)
    return losses, grad1, compare.tree_sub(r1, r0)


def step_flops(conf: dict, mix: dict, ref=None) -> int:
    """Model FLOPs of one training step over the cell's whole graph: the
    reference module's count where it has one, else ``bench/flops.py``'s."""
    ref = ref or harness.reference_module(conf)
    mesh_cf = harness.mesh_of(conf, mix)
    count = getattr(ref, "train_step_flops", flops.train_step_flops)
    return count(conf["model"], *flops.box_graph_size(mesh_cf["elements"], mesh_cf["order"]),
                 mix["batch"])


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
    ref = harness.reference_module(conf)
    rec.units = n
    rec.flops_per_unit = step_flops(conf, mix, ref)
    runtime_peak = trace.memory_peak(cell.devices)
    program = program_memory(prog, params, opt, mix, conf["model"]["node_in"])
    del prog, params, opt          # the program's device buffers are freed

    with span("reference"):
        graph = reference_graph(conf, mix, cell.devices, ref)
        ref_readings = reference_steps(conf, mix, cell.seed, graph, ref=ref)
    print(f"reference {ref.__name__} on {graph.arrays['src'].sharding}: "
          f"{span.total('reference'):.3f} s", file=sys.stderr)
    return {"metrics": {"train_step_ms": rec.window_s / n * 1e3},
            "attempted": n, "failed": failed,
            "readings": compare.train_gaps(prog_readings, ref_readings),
            "memory_runtime_peak_bytes": runtime_peak, "memory_program_bytes": program}


def half_batch_loss(ref):
    """The reference's loss with half of the batch left out: the mean over
    the first half of the nodes (the fault a training cell can have)."""
    def loss(params, xs, graph, precision, remat=True):
        ys = jax.vmap(lambda x: ref.forward(params, x, graph, precision, remat))(xs)
        half = xs.shape[1] // 2
        return jnp.mean(jnp.square(ys - xs)[:, :half])
    return loss


@contextlib.contextmanager
def no_exchange():
    """The program with the halo exchange between chips (Eq. 4c-d) left out:
    each rank keeps its own partial aggregate of the shared nodes. Only
    programs traced inside the block are affected."""
    import repro.core.consistent_mp as cmp
    sync = cmp.halo_sync
    cmp.halo_sync = lambda agg, *args, **kw: agg
    try:
        yield
    finally:
        cmp.halo_sync = sync


def calibrate(conf: dict, mix: dict, devices, seeds: list[int], n_control: int) -> dict:
    """Readings at the cell's own size, the graph and programs built once:
    ``program`` on every seed, compared as a run compares it;
    ``control_high``, the reference at the chip's ``Precision.HIGH`` in the
    program's place, and ``half_batch``, the reference with half of the
    batch left out, on the first ``n_control`` seeds; on a mesh of more than
    one rank also ``no_exchange``, the program with the halo exchange left
    out, on the same seeds."""
    from repro.train.loop import build_execution

    quiet = lambda name: contextlib.nullcontext()  # noqa: E731
    ref = harness.reference_module(conf)
    b1 = conf["optimizer"]["b1"]
    prog = build(conf, mix, devices, seeds[0], quiet)
    graph = reference_graph(conf, mix, devices, ref)
    out = {"program": [], "control_high": [], "half_batch": []}
    wants = {}
    for i, seed in enumerate(seeds):
        _, _, got = checked_steps(prog, mix, seed, b1)
        want = wants[seed] = reference_steps(conf, mix, seed, graph, ref=ref)
        out["program"].append(dict(seed=seed, **compare.train_gaps(got, want)))
        if i < n_control:
            for name, kw in (("control_high", {"precision": jax.lax.Precision.HIGH}),
                             ("half_batch", {"loss_fn": half_batch_loss(ref)})):
                got = reference_steps(conf, mix, seed, graph, ref=ref, **kw)
                out[name].append(dict(seed=seed, **compare.train_gaps(got, want)))
        print(json.dumps({k: v[-1] for k, v in out.items() if v}), flush=True)
    if math.prod(harness.mesh_of(conf, mix)["ranks"]) > 1:
        out["no_exchange"] = []
        with no_exchange():
            ex = build_execution(prog.mesh_dev, prog.pg, prog.sem, prog.gcfg, prog.tcfg, None)
            cut = SimpleNamespace(init=prog.init, step=stepper(ex, quiet))
            for seed in seeds[:n_control]:
                _, _, got = checked_steps(cut, mix, seed, b1)
                out["no_exchange"].append(dict(seed=seed, **compare.train_gaps(got, wants[seed])))
                print(json.dumps({"no_exchange": out["no_exchange"][-1]}), flush=True)
    return out
