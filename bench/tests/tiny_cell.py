"""Run one cell of ``BENCHMARK.json`` at the tiny mesh on as many virtual CPU
devices as it asks for, in a process of its own, and print its result line
as JSON on the last line of standard output:

    python3 bench/tests/tiny_cell.py <cell> [--seed n] [--seconds s] [--fault name]

``--fault`` plants a fault of ``bench/tests/faults.py`` first. The CPU
tests use it for cells on more than one chip.
"""
import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--seed", type=int, default=2**31 + 17)
    ap.add_argument("--seconds", type=float, default=0.3)
    ap.add_argument("--fault")
    args = ap.parse_args(argv)

    from bench import harness
    from bench.tests.conftest import run_tiny, with_r4
    bench = with_r4(harness.load_benchmark())
    chips = harness.workload(bench, args.cell)["chips"]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    res = run_tiny(bench, args.cell, args.seed, args.seconds, args.fault)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
