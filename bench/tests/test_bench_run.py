"""Whole runs of each cell at a tiny mesh on the CPU, and the refusal to run
without a chip."""
import os
import subprocess
import sys

import pytest

from bench import harness
from bench.tests.conftest import limits, run_tiny, with_r4

CELLS = [w["name"] for w in with_r4(harness.load_benchmark())["workloads"]]


def test_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", CELLS[0],
                        "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
                       cwd=harness.ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "TPU" in p.stderr


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_at_a_tiny_mesh(bench, cell):
    res = run_tiny(bench, cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    reported = set(res["metrics"])
    wanted = {m["name"] for m in harness.metrics_of(bench, cell, "end_to_end")}
    assert reported == wanted
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res["checks"]) == list(limits(cell))
