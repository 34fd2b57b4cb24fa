"""A run with the timed path broken underneath comes out not correct: once
for each fault a cell can have. The harness's look for a chip is skipped
and the rest of a run is driven at a tiny mesh on the CPU."""
import jax.numpy as jnp
import pytest

from bench.tests.conftest import run_tiny


def unchanged_state(monkeypatch):
    """The optimizer step returns the parameters and its state unchanged."""
    import repro.train.loop as loop
    monkeypatch.setattr(loop, "adamw_update",
                        lambda grads, state, params, cfg: (params, state, {}))


def half_batch(monkeypatch):
    """Half of the batch (of the graph's nodes) is left out of the loss, the
    mean taken over the rest."""
    import repro.core.distributed as distributed
    mse = distributed.consistent_mse

    def half(y, y_hat, node_inv_mult, axis_names=()):
        n = node_inv_mult.shape[-1]
        return mse(y, y_hat, node_inv_mult * (jnp.arange(n) < n // 2), axis_names)
    monkeypatch.setattr(distributed, "consistent_mse", half)


def altered_answer(monkeypatch):
    """One value of each answer is altered where the engine produces it."""
    import repro.runtime.engine as engine
    scatter = engine.scatter_node_outputs

    def altered(pg, per_rank_y):
        out = scatter(pg, per_rank_y)
        out[0, 0] += 1.0
        return out
    monkeypatch.setattr(engine, "scatter_node_outputs", altered)


@pytest.mark.parametrize("cell,fault", [
    ("large-train-r1", unchanged_state),
    ("large-train-r1", half_batch),
    ("small-train-r1", unchanged_state),
    ("small-train-r1", half_batch),
    ("large-infer-r1", altered_answer),
], ids=lambda v: getattr(v, "__name__", v))
def test_fault_comes_out_not_correct(bench, monkeypatch, cell, fault):
    fault(monkeypatch)
    res = run_tiny(bench, cell)
    assert not res["correct"], res["checks"]
