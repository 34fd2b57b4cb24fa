"""Shared helpers of the benchmark's CPU tests: each cell at a tiny mesh."""
import contextlib
import json
import os
import subprocess
import sys

import pytest

from bench import harness

TINY_MESH = {"elements": [2, 2, 2], "order": 2, "ranks": [1, 1, 1]}

#: the four-chip weak-scaling cell, whose traffic file the harness has but
#: which ``BENCHMARK.json`` lists only once a four-chip machine has set its
#: limits; until then the CPU tests drive it under ``large-train-r1``'s limits
R4 = {"name": "large-train-r4", "config": "paper-large", "traffic": "train-r4", "chips": 4}
R4_LIMITS = "large-train-r1"


def with_r4(bench: dict) -> dict:
    """``bench`` with the four-chip cell among its workloads."""
    if any(w["name"] == R4["name"] for w in bench["workloads"]):
        return bench
    return dict(bench, workloads=[*bench["workloads"], R4])


@pytest.fixture(scope="module")
def bench():
    return with_r4(harness.load_benchmark())


def limits(cell: str) -> dict:
    """The cell's committed limits; the four-chip cell's stand-in."""
    path = harness.BENCH_DIR / "limits" / f"{cell}.json"
    if not path.exists() and cell == R4["name"]:
        path = harness.BENCH_DIR / "limits" / f"{R4_LIMITS}.json"
    return json.loads(path.read_text())


def tiny(bench, cell: str) -> dict:
    """The cell's configuration with its mesh cut to a CPU test's size."""
    conf = harness.config(bench, harness.workload(bench, cell)["config"])
    return dict(conf, mesh=TINY_MESH)


def tiny_mix(bench, cell: str) -> dict:
    """The cell's traffic, its own mesh (where it names one) cut to the
    tiny mesh over the same rank grid."""
    mix = harness.traffic(harness.workload(bench, cell)["traffic"])
    if "mesh" in mix:
        mix = dict(mix, mesh=dict(TINY_MESH, ranks=mix["mesh"]["ranks"]))
    return mix


def run_tiny(bench, cell: str, seed: int = 2**31 + 17, seconds: float = 0.3,
             fault: str | None = None, config: dict | None = None) -> dict:
    """One run of the cell at the tiny mesh on the CPU, limits as committed,
    with the fault ``fault`` of ``bench/tests/faults.py`` planted (a fault
    that returns a context is planted while it is entered): the
    harness's look for a chip is skipped, the rest of a run is driven. A cell
    on more chips than this process has CPU devices runs in a process of its
    own (``bench/tests/tiny_cell.py``) on as many virtual ones."""
    import time

    import jax
    from bench import run
    from bench.tests import faults
    chips = harness.workload(bench, cell)["chips"]
    if len(jax.devices("cpu")) < chips:
        assert config is None, "a configuration is not passed to a process of its own"
        return run_apart(cell, seed, seconds, fault)
    with pytest.MonkeyPatch.context() as mp, contextlib.ExitStack() as stack:
        if fault:
            stack.enter_context(getattr(faults, fault)(mp) or contextlib.nullcontext())
        return run.run_cell(bench, cell, seed, seconds, False, jax.devices("cpu")[:chips],
                            config=config or tiny(bench, cell), lims=limits(cell),
                            mix=tiny_mix(bench, cell), t_start=time.perf_counter())


def run_apart(cell: str, seed: int, seconds: float, fault: str | None) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    args = [sys.executable, str(harness.BENCH_DIR / "tests" / "tiny_cell.py"), cell,
            "--seed", str(seed), "--seconds", str(seconds)]
    p = subprocess.run(args + (["--fault", fault] if fault else []), cwd=harness.ROOT,
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])
