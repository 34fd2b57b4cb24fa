"""Pure-JAX optimizers (no optax offline): AdamW + SGD with the production
features a framework needs — LR schedules (warmup + cosine/linear), global
gradient-norm clipping, decoupled weight decay with a parameter mask,
gradient accumulation, and mixed-precision moments (bf16 m/v option used by
the largest configs to fit HBM).

Optimizer state is a pytree congruent with params, so any sharding applied to
params transfers to the state (ZeRO-style sharded optimizer comes for free
from the param PartitionSpecs).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro import nn as rnn


# ---------------------------------------------------------------------------
# LR schedules
# ---------------------------------------------------------------------------

def warmup_cosine(base_lr: float, warmup_steps: int, total_steps: int,
                  final_frac: float = 0.1) -> Callable:
    def f(step):
        step = step.astype(jnp.float32) if hasattr(step, "astype") else jnp.float32(step)
        warm = base_lr * jnp.minimum(1.0, step / jnp.maximum(warmup_steps, 1))
        t = jnp.clip((step - warmup_steps) / jnp.maximum(total_steps - warmup_steps, 1), 0.0, 1.0)
        cos = base_lr * (final_frac + (1 - final_frac) * 0.5 * (1 + jnp.cos(jnp.pi * t)))
        return jnp.where(step < warmup_steps, warm, cos)
    return f


def constant_lr(base_lr: float) -> Callable:
    return lambda step: jnp.full((), base_lr, jnp.float32)


# ---------------------------------------------------------------------------
# AdamW
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    schedule: Callable = dataclasses.field(default_factory=lambda: constant_lr(1e-3))
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.0
    clip_norm: Optional[float] = 1.0
    moment_dtype: jnp.dtype = jnp.float32     # bf16 halves optimizer HBM
    # decay mask: params whose path matches any of these substrings are
    # excluded from weight decay (norms, biases, embeddings typically)
    no_decay_substrings: tuple = ("ln", "norm", "bias", "b",)


def init_adamw(params, cfg: AdamWConfig):
    def zeros(p):
        return jnp.zeros(p.shape, cfg.moment_dtype)
    return {
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
        "step": jnp.zeros((), jnp.int32),
    }


def _decay_mask(params, cfg: AdamWConfig):
    paths = jax.tree_util.tree_flatten_with_path(params)[0]
    flags = []
    for path, _ in paths:
        keystr = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        last = keystr.split("/")[-1]
        exclude = any(s == last or (len(s) > 1 and s in keystr) for s in cfg.no_decay_substrings)
        flags.append(0.0 if exclude else 1.0)
    return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(params), flags)


def clip_by_global_norm(grads, max_norm: float):
    norm = rnn.global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: g * scale.astype(g.dtype), grads), norm


def adamw_update(grads, state, params, cfg: AdamWConfig):
    """One AdamW step. params/grads may be lower precision; math in fp32.
    Its ops carry the device scope ``adamw``."""
    with jax.named_scope("adamw"):
        step = state["step"] + 1
        lr = cfg.schedule(step)
        if cfg.clip_norm is not None:
            grads, gnorm = clip_by_global_norm(grads, cfg.clip_norm)
        else:
            gnorm = rnn.global_norm(grads)
        mask = _decay_mask(params, cfg)

        b1, b2 = cfg.b1, cfg.b2
        bc1 = 1 - b1 ** step.astype(jnp.float32)
        bc2 = 1 - b2 ** step.astype(jnp.float32)

        def upd(p, g, m, v, dmask):
            g32 = g.astype(jnp.float32)
            m32 = m.astype(jnp.float32) * b1 + (1 - b1) * g32
            v32 = v.astype(jnp.float32) * b2 + (1 - b2) * jnp.square(g32)
            mhat = m32 / bc1
            vhat = v32 / bc2
            step_vec = mhat / (jnp.sqrt(vhat) + cfg.eps)
            p32 = p.astype(jnp.float32)
            p32 = p32 - lr * (step_vec + cfg.weight_decay * dmask * p32)
            return p32.astype(p.dtype), m32.astype(cfg.moment_dtype), v32.astype(cfg.moment_dtype)

        out = jax.tree.map(upd, params, grads, state["m"], state["v"], mask)
        # unzip the 3-tuples
        new_params = jax.tree.map(lambda t: t[0], out, is_leaf=lambda t: isinstance(t, tuple))
        new_m = jax.tree.map(lambda t: t[1], out, is_leaf=lambda t: isinstance(t, tuple))
        new_v = jax.tree.map(lambda t: t[2], out, is_leaf=lambda t: isinstance(t, tuple))
        new_state = {"m": new_m, "v": new_v, "step": step}
        return new_params, new_state, {"lr": lr, "grad_norm": gnorm}


# ---------------------------------------------------------------------------
# SGD (paper-style consistency experiments)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SGDConfig:
    lr: float = 1e-3
    momentum: float = 0.0


def init_sgd(params, cfg: SGDConfig):
    if cfg.momentum == 0.0:
        return {"step": jnp.zeros((), jnp.int32)}
    return {"mu": rnn.tree_zeros_like(params), "step": jnp.zeros((), jnp.int32)}


def sgd_update(grads, state, params, cfg: SGDConfig):
    step = state["step"] + 1
    if cfg.momentum == 0.0:
        new_params = jax.tree.map(lambda p, g: p - cfg.lr * g.astype(p.dtype), params, grads)
        return new_params, {"step": step}, {}
    mu = jax.tree.map(lambda m, g: cfg.momentum * m + g.astype(m.dtype), state["mu"], grads)
    new_params = jax.tree.map(lambda p, m: p - cfg.lr * m.astype(p.dtype), params, mu)
    return new_params, {"mu": mu, "step": step}, {}


# ---------------------------------------------------------------------------
# gradient accumulation wrapper
# ---------------------------------------------------------------------------

def accumulate_gradients(grad_fn, n_micro: int):
    """Wrap grad_fn(params, batch)->(loss, grads) to average over micro-batches.

    ``batch`` leaves must have a leading [n_micro, ...] axis; the scan keeps
    peak activation memory at one micro-batch.
    """
    def wrapped(params, batch):
        def body(carry, micro):
            acc_loss, acc_g = carry
            loss, g = grad_fn(params, micro)
            return (acc_loss + loss, rnn.tree_add(acc_g, g)), None

        zero = (jnp.zeros((), jnp.float32), rnn.tree_zeros_like(params))
        (loss, grads), _ = jax.lax.scan(body, zero, batch)
        return loss / n_micro, rnn.tree_scale(grads, 1.0 / n_micro)
    return wrapped
