"""Solver-in-the-loop serving launcher (CLI): resident GNN inference engine.

    PYTHONPATH=src python -m repro.launch.serve \
        --ckpt-dir /tmp/repro_serve_ckpt --requests 32 --batch-slots 4 \
        --rollout-steps 2 --producers 2 --bootstrap-steps 20

Loads a fingerprinted training checkpoint into a resident
:class:`repro.runtime.engine.InferenceEngine`, registers the mesh (the
``ShardedGraph`` + ``NMPPlan`` build is cached by mesh hash), warms the
jitted batch-slot program, then emulates a solver feed: producer threads
stream Taylor-Green snapshots through the engine's bounded request queue
and the CLI reports per-request latency percentiles and steady-state
throughput.

With an empty ``--ckpt-dir`` and ``--bootstrap-steps N > 0``, a short
training run creates a fingerprinted checkpoint first (demo convenience —
the engine itself refuses unfingerprinted checkpoints).  The earlier LM
serving toy lives on as ``examples/serve_lm.py``.
"""
import argparse
import time

import numpy as np
import jax

from repro.core import GNNConfig, box_mesh, partition_mesh
from repro.core.mesh_gen import taylor_green_velocity
from repro.ckpt import checkpoint as ckpt
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.runtime.engine import EngineConfig, InferenceEngine
from repro.train.loop import TrainConfig, train_consistent_gnn

DT = 0.05


def _bootstrap(args, sem):
    """Create a fingerprinted checkpoint via a short training run."""
    R = len(jax.devices())
    mesh_dev = make_mesh((1, R), ("data", "graph"))
    pg = partition_mesh(sem, (R, 1, 1), method=args.partitioner)
    tcfg = TrainConfig(
        n_steps=args.bootstrap_steps,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=max(1, args.bootstrap_steps // 2),
        halo_mode=args.halo_mode if R > 1 else "none",
        partitioner=args.partitioner,
        log_every=max(1, args.bootstrap_steps // 4))
    print(f"[serve] no committed checkpoint under {args.ckpt_dir}; "
          f"bootstrapping with a {args.bootstrap_steps}-step training run")
    train_consistent_gnn(mesh_dev, pg, sem, GNNConfig.small(), tcfg)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt-dir", default="/tmp/repro_serve_ckpt",
                    help="fingerprinted checkpoint directory to serve from")
    ap.add_argument("--mesh", default="4,4,2",
                    help="box mesh elements per dim, e.g. 4,4,2")
    ap.add_argument("--p", type=int, default=2, help="SEM polynomial order")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--rollout-steps", type=int, default=1,
                    help="prediction horizon K per request")
    ap.add_argument("--producers", type=int, default=2,
                    help="concurrent solver-feed producer threads")
    ap.add_argument("--max-pending", type=int, default=16,
                    help="bounded request queue depth (backpressure point)")
    ap.add_argument("--partitioner", default="block",
                    choices=["block", "spectral"])
    ap.add_argument("--halo-mode", default="a2a",
                    choices=["a2a", "neighbor"])
    ap.add_argument("--bootstrap-steps", type=int, default=20,
                    help="train this many steps to create a checkpoint when "
                         "--ckpt-dir has none (0 = refuse instead)")
    args = ap.parse_args()
    for name in ("requests", "batch_slots", "rollout_steps", "producers",
                 "max_pending"):
        if getattr(args, name) < 1:
            ap.error(f"--{name.replace('_', '-')} must be >= 1, got "
                     f"{getattr(args, name)}")

    enable_compile_cache()
    sem = box_mesh(tuple(int(v) for v in args.mesh.split(",")), p=args.p)
    if not ckpt.committed_steps(args.ckpt_dir):
        if args.bootstrap_steps < 1:
            ap.error(f"no committed checkpoint under {args.ckpt_dir} and "
                     "--bootstrap-steps 0: nothing to serve")
        _bootstrap(args, sem)

    engine = InferenceEngine(
        args.ckpt_dir, GNNConfig.small(),
        EngineConfig(batch_slots=args.batch_slots,
                     rollout_steps=args.rollout_steps,
                     max_pending=args.max_pending,
                     halo_mode=args.halo_mode,
                     partitioner=args.partitioner))
    print(f"[serve] params from step {engine.ckpt_step}, trained mesh "
          f"{engine.fingerprint['mesh_hash']} "
          f"(n_global={engine.fingerprint['n_global']}), serving on "
          f"R={engine.R} device(s)")
    mesh_hash = engine.register_mesh(sem)
    engine.warmup()

    def snapshot_fn(step: int):
        return taylor_green_velocity(sem.coords,
                                     t=(step * DT) % 2.0).astype(np.float32)

    with engine:
        t0 = time.perf_counter()
        results = list(engine.stream(mesh_hash, snapshot_fn, args.requests,
                                     n_producers=args.producers))
        wall = time.perf_counter() - t0

    lat = np.sort([r.latency_s for _, r in results]) * 1e3
    p50 = float(np.percentile(lat, 50))
    p95 = float(np.percentile(lat, 95))
    st = engine.stats
    print(f"[serve] {len(results)} requests in {wall:.2f}s "
          f"({len(results) / wall:.1f} req/s) | latency p50 {p50:.1f} ms, "
          f"p95 {p95:.1f} ms | {st['batches']} batches, "
          f"{st['padded_slots']} padded slots, graph cache "
          f"{st['cache_builds']} build(s) / {st['cache_hits']} hit(s)")


if __name__ == "__main__":
    main()
