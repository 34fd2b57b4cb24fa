"""A run with the timed path broken underneath comes out not correct: once
for each fault a cell can have. The harness's look for a chip is skipped
and the rest of a run is driven at a tiny mesh on the CPU; a cell on more
chips than this process has runs in a process of its own on as many
virtual CPU devices."""
import pytest

from bench.tests.conftest import run_tiny
from bench.tests.faults import (
    altered_answer, half_batch, no_exchange, no_grad_sync, unchanged_state,
)


@pytest.mark.parametrize("cell,fault", [
    ("large-train-r1", unchanged_state),
    ("large-train-r1", half_batch),
    ("small-train-r1", unchanged_state),
    ("small-train-r1", half_batch),
    ("large-infer-r1", altered_answer),
    ("large-train-r4", unchanged_state),
    ("large-train-r4", half_batch),
    ("large-train-r4", no_exchange),
    ("large-train-r4", no_grad_sync),
], ids=lambda v: getattr(v, "__name__", v))
def test_fault_comes_out_not_correct(bench, cell, fault):
    res = run_tiny(bench, cell, fault=fault.__name__)
    assert not res["correct"], res["checks"]
