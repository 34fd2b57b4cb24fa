"""Inference traffic: a closed-loop client of ``repro.runtime.engine``.

The client stands for a running solver coupled to the surrogate: it sends
its current field through ``InferenceEngine.submit`` and waits for the
prediction before it sends the next. The engine loads its weights from a
checkpoint, so set-up makes the weights on the device in one jitted call
from the seed and writes them as one, under the run's temporary directory.
The fields are Taylor-Green snapshots at times drawn from the seed, made
before the window; request ``i`` sends field ``i`` modulo the pool.

After the window a sample of the requests, drawn from the seed, with the
last one in it, is compared with the forward pass of the plain reference
the configuration names (``bench/reference.py`` by default).
``calibrate`` gives the readings the limits are set from
(``bench/calibrate.py``).
"""
from __future__ import annotations

import contextlib
import json
import math
import shutil
import statistics
import sys
import tempfile
import time
from types import SimpleNamespace

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from bench import compare, flops, harness, trace


def fields(conf: dict, mix: dict, coords: np.ndarray, seed: int, ref=None) -> list[np.ndarray]:
    """The pool of snapshots the client sends, at times drawn from the seed."""
    rng = np.random.default_rng(seed)
    ref = ref or harness.reference_module(conf)
    return [ref.taylor_green(coords, t, mix["nu"])
            for t in rng.uniform(0.0, mix["t_period"], mix["pool"])]


def build(conf: dict, mix: dict, devices, seed: int, span) -> SimpleNamespace:
    """The engine with the seed's weights and the cell's mesh registered."""
    from repro.ckpt import checkpoint as ckpt
    from repro.core import GNNConfig, box_mesh, init_gnn
    from repro.runtime.engine import EngineConfig, InferenceEngine
    from repro.train.loop import mesh_fingerprint_hash

    mesh_cf = harness.mesh_of(conf, mix)
    gcfg = GNNConfig(name=conf["name"], **conf["model"])
    ranks = tuple(mesh_cf["ranks"])
    with span("mesh_gen"):
        sem = box_mesh(tuple(mesh_cf["elements"]), p=mesh_cf["order"])
    mesh_dev = jax.make_mesh((1, math.prod(ranks)), ("data", "graph"), devices=devices,
                             axis_types=(AxisType.Auto,) * 2)
    ckpt_dir = tempfile.mkdtemp(prefix="bench-ckpt-")
    try:
        with span("init"):
            params = jax.jit(lambda k: init_gnn(k, gcfg))(jax.random.PRNGKey(seed))
            ckpt.save(ckpt_dir, 0, {"params": params}, extra={"fingerprint": {
                "mesh_hash": mesh_fingerprint_hash(sem), "n_global": sem.n_nodes,
                "hidden": gcfg.hidden, "n_levels": gcfg.n_levels}})
            del params
            engine = InferenceEngine(
                ckpt_dir, gcfg, EngineConfig(batch_slots=mix["batch_slots"],
                                             rollout_steps=mix["rollout_steps"]),
                mesh_dev=mesh_dev)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    with span("graph_build"):
        mesh_hash = engine.register_mesh(sem, rank_grid=ranks)
    return SimpleNamespace(engine=engine, mesh_hash=mesh_hash, sem=sem, coords=sem.coords,
                           gcfg=gcfg, ranks=ranks)


def program_memory(prog, mix: dict) -> int | None:
    """The footprint of the program the window runs, by the compiler: the
    rollout eval as ``make_rollout_step_fns`` builds it, over a plan and
    graph made the way ``register_mesh`` makes them, at the engine's
    batch-slot shape. The engine's own entry is not public, so this builds
    the mesh's partition and graph once more, after the window."""
    from repro.core import partition_mesh
    from repro.core.graph_state import NMPPlan, ShardedGraph, as_graph
    from repro.train.rollout import make_rollout_step_fns

    mesh, gcfg, k = prog.engine.mesh_dev, prog.gcfg, mix["rollout_steps"]

    def rollout_eval():
        pg = partition_mesh(prog.sem, prog.ranks)
        plan = NMPPlan.build(pg, "none", axis="graph")
        graph = ShardedGraph.build(pg, prog.sem.coords, plan)
        plan = plan.autotune(graph, hidden=gcfg.hidden)
        print(f"plan: {plan.policy()}", file=sys.stderr)
        graph = as_graph(graph)
        gs = jax.tree.map(
            lambda a, spec: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                                 sharding=NamedSharding(mesh, spec)),
            graph, graph.specs("graph"), is_leaf=lambda v: isinstance(v, P))
        shape = (mix["batch_slots"], pg.R, pg.n_pad, gcfg.node_in)
        x = jax.ShapeDtypeStruct(shape, jnp.float32,
                                 sharding=NamedSharding(mesh, P(("data",), "graph")))
        seq = jax.ShapeDtypeStruct(shape[:1] + (k,) + shape[1:], jnp.float32,
                                   sharding=NamedSharding(mesh, P(("data",), None, "graph")))
        fn = make_rollout_step_fns(mesh, gcfg, plan, k)[0]
        return fn.lower(prog.engine.params, x, seq, x, gs)
    return harness.program_bytes("rollout_eval", rollout_eval)


def reference_graph(conf: dict, mix: dict, ref=None):
    """The reference's graph of the cell's mesh."""
    mesh_cf = harness.mesh_of(conf, mix)
    return (ref or harness.reference_module(conf)).Graph(mesh_cf["elements"], mesh_cf["order"])


def reference_predictions(conf: dict, seed: int, xs: dict, graph, precision=None,
                          ref=None) -> dict:
    """The reference's prediction for each field of ``xs`` (key -> field),
    at ``precision`` (the reference's own ``HIGHEST`` by default)."""
    ref = ref or harness.reference_module(conf)
    precision = ref.HIGHEST if precision is None else precision
    params = jax.jit(lambda k: ref.init_params(k, conf["model"]))(
        jax.random.PRNGKey(seed))
    forward = jax.jit(lambda p, x, g: ref.forward(p, x, g, precision))
    return {k: np.asarray(forward(params, x, graph.arrays)) for k, x in xs.items()}


def run(cell) -> dict:
    conf, mix, rec = cell.config, cell.mix, cell.record
    span = rec.spans
    ref = harness.reference_module(conf)
    prog = build(conf, mix, cell.devices, cell.seed, span)
    engine, pool = prog.engine, fields(conf, mix, prog.coords, cell.seed, ref)
    rng = np.random.default_rng(cell.seed + 1)

    kept: dict[int, np.ndarray] = {}
    latencies: list[float] = []
    failed = 0
    with engine:
        with span("warmup"):
            engine.warmup()
            for i in range(mix["warm_requests"]):
                engine.submit(prog.mesh_hash, pool[i % len(pool)]).result()
        rec.setup_end = time.perf_counter()
        with trace.capture(cell.trace, rec):
            t0 = time.perf_counter()
            with span("window"):
                while time.perf_counter() - t0 < cell.seconds:
                    i = len(latencies) + failed
                    keep = rng.random() < mix["sample_share"]
                    t_sent = time.perf_counter()
                    try:
                        with span("request"):
                            with span("submit"):
                                fut = engine.submit(prog.mesh_hash, pool[i % len(pool)],
                                                    step=i)
                            with span("wait"):
                                preds = fut.result(timeout=mix["timeout_s"]).preds
                    except Exception as e:      # counted; the run goes on
                        print(f"request {i} failed: {e!r}", file=sys.stderr)
                        failed += 1
                        if engine.closed:
                            break
                        continue
                    latencies.append(time.perf_counter() - t_sent)
                    if keep:
                        kept[i] = preds[0]
                    last = (i, preds[0])
            rec.window = (t0, time.perf_counter())
        runtime_peak = trace.memory_peak(cell.devices)
        program = program_memory(prog, mix)
    rec.units = len(latencies)
    count = getattr(ref, "forward_flops", flops.forward_flops)
    mesh_cf = harness.mesh_of(conf, mix)
    rec.flops_per_unit = mix["rollout_steps"] * count(
        conf["model"], *flops.box_graph_size(mesh_cf["elements"], mesh_cf["order"]))
    del prog, engine               # the program's device buffers are freed
    if latencies:
        kept[last[0]] = last[1]

    graph = reference_graph(conf, mix, ref)
    refs = reference_predictions(conf, cell.seed,
                                 {f: pool[f] for f in {i % len(pool) for i in kept}}, graph,
                                 ref=ref)
    gap = max((compare.prediction_gap(p, refs[i % len(pool)]) for i, p in kept.items()),
              default=math.inf)
    print(f"compared {len(kept)} of {len(latencies)} predictions", file=sys.stderr)
    metrics = {}
    if len(latencies) >= 2:
        metrics = {"infer_ms_p50": statistics.median(latencies) * 1e3,
                   "infer_ms_p95": statistics.quantiles(latencies, n=20)[18] * 1e3}
    return {"metrics": metrics, "attempted": len(latencies) + failed, "failed": failed,
            "readings": {"pred_gap": gap},
            "memory_runtime_peak_bytes": runtime_peak, "memory_program_bytes": program}


def calibrate(conf: dict, mix: dict, devices, seeds: list[int], n_control: int) -> dict:
    """Readings at the cell's own size, the engine and its programs built
    once: ``program``, every field of the seed's pool through
    ``InferenceEngine.submit`` with the seed's weights, compared as a run
    compares it; ``control_high``, the reference at the chip's
    ``Precision.HIGH`` in the program's place, on the first ``n_control``
    seeds."""
    from repro.core import init_gnn

    ref = harness.reference_module(conf)
    prog = build(conf, mix, devices, seeds[0], lambda name: contextlib.nullcontext())
    engine = prog.engine
    init = jax.jit(lambda key: init_gnn(key, prog.gcfg))
    replicated = NamedSharding(engine.mesh_dev, P())
    graph = reference_graph(conf, mix, ref)
    out = {"program": [], "control_high": []}
    with engine:
        engine.warmup()
        for i, seed in enumerate(seeds):
            # the weights the engine would load from this seed's checkpoint
            engine.params = jax.device_put(init(jax.random.PRNGKey(seed)), replicated)
            pool = dict(enumerate(fields(conf, mix, prog.coords, seed, ref)))
            got = {k: engine.submit(prog.mesh_hash, x).result().preds[0]
                   for k, x in pool.items()}
            want = reference_predictions(conf, seed, pool, graph, ref=ref)
            out["program"].append({"seed": seed, "pred_gap": max(
                compare.prediction_gap(got[k], want[k]) for k in pool)})
            if i < n_control:
                ctrl = reference_predictions(conf, seed, pool, graph, jax.lax.Precision.HIGH,
                                             ref)
                out["control_high"].append({"seed": seed, "pred_gap": max(
                    compare.prediction_gap(ctrl[k], want[k]) for k in pool)})
            print(json.dumps({k: v[-1] for k, v in out.items() if v}), flush=True)
    return out
