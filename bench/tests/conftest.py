"""Shared helpers of the benchmark's CPU tests: each cell at a tiny mesh."""
import pytest

from bench import harness

TINY_MESH = {"elements": [2, 2, 2], "order": 2, "ranks": [1, 1, 1]}


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark()


def tiny(bench, cell: str) -> dict:
    """The cell's configuration with its mesh cut to a CPU test's size."""
    conf = harness.config(bench, harness.workload(bench, cell)["config"])
    return dict(conf, mesh=TINY_MESH)


def run_tiny(bench, cell: str, seed: int = 2**31 + 17, seconds: float = 0.3) -> dict:
    """One run of the cell at the tiny mesh on the CPU, limits as committed:
    the harness's look for a chip is skipped, the rest of a run is driven."""
    import time

    import jax
    from bench import run
    return run.run_cell(bench, cell, seed, seconds, False, jax.devices("cpu")[:1],
                        config=tiny(bench, cell), t_start=time.perf_counter())
