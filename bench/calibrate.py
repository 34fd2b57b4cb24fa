#!/usr/bin/env python3
"""The readings that a cell's correctness limits are set from, on the chip at
the cell's own size, in one process (the graph and the programs are built
once):

    python3 bench/calibrate.py --workload <name> --first-seed <n> --seeds 12 \\
        --control-seeds 3 --out <file.json>

The cell's driver (``bench/drivers/<kind>.py``) gives the readings through
its ``calibrate``:

* ``program``: sound runs of the program on ``--seeds`` seeds, each compared
  with the reference as ``bench/run.py`` compares it;
* ``control_high``: the reference computed at the chip's own
  ``Precision.HIGH``, the step below the configuration's ``HIGHEST``, put
  in the program's place, on the first ``--control-seeds`` seeds;
* ``half_batch`` (training): the reference with half of the batch left out,
  its loss the mean over the first half of the nodes, on the same seeds.

A state left unchanged reads 1 on ``grad_gap`` and ``change_gap`` by the
comparison's own measure and needs no run. The output, with the largest
program reading and the smallest control or fault reading of each number
under ``summary``, is kept as ``bench/calibration/<cell>.json``. The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def summary(out: dict) -> dict:
    """The largest reading of each number over the program's seeds, and the
    smallest over each control's or fault's."""
    res = {}
    for kind, rows in out.items():
        agg = max if kind == "program" else min
        res[kind] = {k: agg(r[k] for r in rows) for k in rows[0] if k != "seed"} if rows else {}
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax
    from bench import harness, run
    from repro.launch.compile_cache import enable_compile_cache
    bench = harness.load_benchmark(ROOT)
    wl = harness.workload(bench, args.workload)
    devices = run.require_chips(wl["chips"])
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    conf, mix = harness.config(bench, wl["config"]), harness.traffic(wl["traffic"])
    seeds = [args.first_seed + 1_000_003 * i for i in range(args.seeds)]
    out = harness.driver(mix["kind"]).calibrate(conf, mix, devices, seeds, args.control_seeds)
    out["summary"] = summary(out)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(out, indent=1))
    print(json.dumps(out["summary"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
