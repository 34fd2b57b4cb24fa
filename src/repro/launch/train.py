"""Production training launcher (CLI): consistent GNN on partitioned meshes.

    PYTHONPATH=src python -m repro.launch.train \
        --elements 4 4 2 --order 3 --ranks 2 2 1 --steps 200 \
        --halo neighbor --model small --ckpt /tmp/ckpt

Uses every substrate layer: SEM mesh gen -> partitioner -> shard_map step
with real halo collectives -> AdamW -> async checkpoints -> straggler
monitor. It runs on the devices JAX finds; to run the multi-device grid on
a CPU host, give it fake host devices yourself:
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8``.

``--rollout-steps K`` (K > 1) switches to autoregressive rollout training
(repro.train.rollout): the model is scanned over its own predictions for K
steps with a per-step halo-consistent loss; ``--pushforward-noise`` adds the
stop-gradient step-1 perturbation that emulates inference-time drift.
"""
import argparse

import numpy as np

from repro.core import GNNConfig, NMPPlan, box_mesh, partition_mesh
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.runtime.fault_tolerance import ResilientConfig
from repro.train.loop import TrainConfig, train_consistent_gnn


def main(argv=None) -> dict:
    """Parse ``argv`` (``sys.argv[1:]`` when None), train, print the loss
    summary and return the training history."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--elements", type=int, nargs=3, default=[4, 4, 2])
    ap.add_argument("--order", type=int, default=3)
    ap.add_argument("--ranks", type=int, nargs=3, default=[2, 2, 1])
    ap.add_argument("--data-parallel", type=int, default=2)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--lr", type=float, default=2e-3)
    ap.add_argument("--halo", default="neighbor", choices=["neighbor", "a2a", "none"])
    ap.add_argument("--model", default="small", choices=["small", "large"])
    ap.add_argument("--ckpt", default=None,
                    help="plain fire-and-forget checkpoint dir (no resume); "
                         "for crash recovery + elastic resume use --ckpt-dir")
    ap.add_argument("--ckpt-dir", default=None,
                    help="resilient checkpoint dir: auto-resumes from the "
                         "newest valid checkpoint (elastically — the "
                         "checkpoint may come from a different --ranks or "
                         "--partitioner), recovers from crashes, and writes "
                         "fingerprinted manifests (see CONTRIBUTING.md "
                         "'Elastic resume')")
    ap.add_argument("--ckpt-every", type=int, default=50,
                    help="steps between periodic checkpoints (with --ckpt-dir)")
    ap.add_argument("--max-restarts", type=int, default=3,
                    help="in-process crash recoveries before giving up "
                         "(with --ckpt-dir)")
    ap.add_argument("--mp-backend", default="xla", choices=["xla", "fused"],
                    help="NMP hot-loop backend (fused = Pallas kernel)")
    ap.add_argument("--mp-interpret", action="store_true",
                    help="run the fused kernels via the Pallas interpreter")
    ap.add_argument("--mp-schedule", default="blocking",
                    choices=["blocking", "overlap", "auto"],
                    help="halo/compute schedule (overlap hides the exchange "
                         "behind interior-edge work; auto measures both on "
                         "this graph x rank count and commits to the winner)")
    ap.add_argument("--partitioner", default="block",
                    choices=["block", "spectral"],
                    help="mesh decomposition: block = NekRS-style element "
                         "blocks along --ranks; spectral = recursive "
                         "spectral bisection + KL refinement "
                         "(repro.core.partition_quality) — lower halo "
                         "volume on stretched/unstructured meshes, "
                         "identical results either way")
    ap.add_argument("--mp-precision", default="fp32",
                    choices=["fp32", "bf16"],
                    help="edge-MLP matmul precision: bf16 runs the matmuls "
                         "with bf16 operands and fp32 accumulation (faster "
                         "on MXU hardware; not bit-stable with fp32 — see "
                         "CONTRIBUTING.md)")
    ap.add_argument("--levels", type=int, default=1,
                    help="multilevel message-passing depth: 1 = flat NMP; "
                         ">1 adds a consistent coarse-grid V-cycle (level 1 "
                         "= element centroids, deeper levels cluster the "
                         "element grid 2x per axis — repro.core.coarsen)")
    ap.add_argument("--coarse-mp-layers", type=int, default=2,
                    help="NMP layers smoothing each coarse level")
    ap.add_argument("--rollout-steps", type=int, default=1,
                    help="K > 1 trains autoregressively: the model is "
                         "scanned over its OWN predictions for K steps with "
                         "a per-step halo-consistent loss "
                         "(repro.train.rollout)")
    ap.add_argument("--pushforward-noise", type=float, default=0.0,
                    help="stddev of the stop-gradient pushforward noise "
                         "added to the rollout's initial state (emulates "
                         "inference-time drift; needs --rollout-steps > 1)")
    args = ap.parse_args(argv)
    if args.rollout_steps < 1:
        ap.error("--rollout-steps must be >= 1")
    if args.pushforward_noise and args.rollout_steps == 1:
        ap.error("--pushforward-noise needs --rollout-steps > 1 (one-step "
                 "training never feeds predictions back)")
    if args.ckpt and args.ckpt_dir:
        ap.error("--ckpt and --ckpt-dir are mutually exclusive (plain "
                 "fire-and-forget saves vs resilient auto-resume)")

    enable_compile_cache()
    sem = box_mesh(tuple(args.elements), p=args.order)
    R = int(np.prod(args.ranks))
    cfg = GNNConfig.small() if args.model == "small" else GNNConfig.large()
    hierarchy = None
    if args.levels > 1:
        import dataclasses

        from repro.core.coarsen import build_hierarchy
        cfg = dataclasses.replace(cfg, n_levels=args.levels,
                                  coarse_mp_layers=args.coarse_mp_layers,
                                  coarse_edge_in=sem.dim + 1)
        node2part = None
        if args.partitioner == "spectral":
            from repro.core.partition_quality import mesh_node2part
            node2part = mesh_node2part(sem, R)
        hierarchy = build_hierarchy(sem, tuple(args.ranks), args.levels,
                                    node2part=node2part)
        pg = hierarchy.levels[0]
        sizes = " -> ".join(str(s) for s in hierarchy.level_sizes())
        print(f"multilevel hierarchy: {sizes} nodes per level")
    else:
        pg = partition_mesh(sem, tuple(args.ranks), method=args.partitioner)
    mesh_dev = make_mesh((args.data_parallel, R), ("data", "graph"))
    print(f"mesh: {sem.n_elem} elems p={args.order} ({sem.n_nodes} nodes); "
          f"R={R} sub-graphs x DP={args.data_parallel}; halo={args.halo}; "
          f"partitioner={args.partitioner}; levels={args.levels}; "
          f"rollout K={args.rollout_steps}")

    policy = NMPPlan(backend=args.mp_backend, interpret=args.mp_interpret,
                     schedule=args.mp_schedule, precision=args.mp_precision)
    resilience = None
    if args.ckpt_dir:
        resilience = ResilientConfig(ckpt_dir=args.ckpt_dir,
                                     ckpt_every=args.ckpt_every,
                                     max_restarts=args.max_restarts)
    tcfg = TrainConfig(n_steps=args.steps, batch=args.batch, lr=args.lr,
                       halo_mode=args.halo, ckpt_dir=args.ckpt, plan=policy,
                       rollout_steps=args.rollout_steps,
                       pushforward_noise=args.pushforward_noise,
                       partitioner=args.partitioner, resilience=resilience)
    hist = train_consistent_gnn(mesh_dev, pg, sem, cfg, tcfg,
                                hierarchy=hierarchy)
    if args.mp_schedule == "auto":
        print(f"schedule auto -> {hist['schedule']}")
    if hist.get("elastic"):
        el = hist["elastic"]
        print(f"elastic resume at step {el['step']}: "
              f"R={el['from_ranks']}/{el['from_partitioner']} -> "
              f"R={el['to_ranks']}/{el['to_partitioner']}")
    if hist.get("restarts"):
        print(f"recovered from {hist['restarts']} crash(es)")
    print(f"loss {hist['losses'][0]:.6f} -> {hist['losses'][-1]:.6f} "
          f"({len(hist['losses'])} steps, {hist['straggler_events']} straggler events)")
    return hist


if __name__ == "__main__":
    main()
