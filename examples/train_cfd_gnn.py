"""End-to-end driver: distributed training of the consistent mesh GNN.

Trains the paper's 'small' GNN on Taylor-Green-vortex snapshots over a
partitioned SEM mesh with REAL collectives (shard_map over a (data, graph)
device mesh), AdamW, async checkpointing + restart, and straggler monitoring.
Needs 8 devices; on a CPU host, give it fake host devices:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/train_cfd_gnn.py [--steps 300]
"""
import argparse

from repro.core import GNNConfig, box_mesh, partition_mesh
from repro.launch.mesh import make_mesh
from repro.train.loop import TrainConfig, train_consistent_gnn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--halo", default="neighbor", choices=["neighbor", "a2a", "none"])
    ap.add_argument("--ckpt", default="/tmp/repro_cfd_ckpt")
    args = ap.parse_args()

    sem_mesh = box_mesh((4, 4, 2), p=3)
    pg = partition_mesh(sem_mesh, (2, 2, 1))           # R=4 spatial partitions
    mesh_dev = make_mesh((2, 4), ("data", "graph"))    # DP=2 x graph=4

    cfg = GNNConfig.small()
    tcfg = TrainConfig(n_steps=args.steps, batch=2, halo_mode=args.halo,
                       ckpt_dir=args.ckpt, ckpt_every=100, lr=2e-3)
    hist = train_consistent_gnn(mesh_dev, pg, sem_mesh, cfg, tcfg)
    losses = hist["losses"]
    print(f"steps={len(losses)}  loss: {losses[0]:.6f} -> {losses[-1]:.6f}  "
          f"(straggler events: {hist['straggler_events']})")
    assert losses[-1] < losses[0], "training should reduce the loss"
    print(f"checkpoints in {args.ckpt}")


if __name__ == "__main__":
    main()
