"""Faults a cell's timed path can have, each planted with a pytest
``MonkeyPatch`` in the program under test, or returned as a context that
plants it while it is entered."""
import jax.numpy as jnp


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


def no_exchange(monkeypatch):
    """The halo exchange between chips (Eq. 4c-d) is left out: each rank
    keeps its own partial aggregate of the nodes it shares. The training
    driver's own fault, the one ``bench/calibrate.py`` reads on the chip."""
    from bench import harness
    return harness.driver("train").no_exchange()


def no_grad_sync(monkeypatch):
    """The gradients' mean over the chips is left out: each chip's step
    takes its own partial gradient."""
    import repro.core.distributed as distributed
    monkeypatch.setattr(distributed.jax.lax, "pmean", lambda x, axis_name, **kw: x)


def altered_answer(monkeypatch):
    """One value of each answer is altered where the engine produces it."""
    import repro.runtime.engine as engine
    scatter = engine.scatter_node_outputs

    def altered(pg, per_rank_y):
        out = scatter(pg, per_rank_y)
        out[0, 0] += 1.0
        return out
    monkeypatch.setattr(engine, "scatter_node_outputs", altered)
