"""Minimal pure-JAX neural-net building blocks (no flax/haiku available offline).

Parameters are plain pytrees (nested dicts of jnp arrays); every module is a
pair of functions: ``init_*(key, ...) -> params`` and ``apply`` (the forward
fn). Initializers follow standard fan-in scaling.
"""
from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp

Params = dict


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def glorot(key, shape, dtype=jnp.float32):
    fan_in, fan_out = shape[-2], shape[-1]
    lim = math.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, shape, dtype, -lim, lim)


def lecun_normal(key, shape, dtype=jnp.float32):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    return jax.random.normal(key, shape, dtype) * math.sqrt(1.0 / fan_in)


def zeros(_key, shape, dtype=jnp.float32):
    return jnp.zeros(shape, dtype)


# ---------------------------------------------------------------------------
# dense / layernorm / mlp
# ---------------------------------------------------------------------------

def init_dense(key, d_in: int, d_out: int, dtype=jnp.float32, bias: bool = True) -> Params:
    kw, _ = jax.random.split(key)
    p = {"w": glorot(kw, (d_in, d_out), dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p: Params, x: jnp.ndarray, precision: str | None = None) -> jnp.ndarray:
    """``precision="bf16"`` runs the matmul with bf16 operands accumulating
    into fp32 (``preferred_element_type``) — the same mixed-precision policy
    the fused Pallas kernels apply; ``None``/``"fp32"`` is a true fp32
    matmul (``Precision.HIGHEST``: a TPU's default for fp32 operands is a
    single bf16 pass, which the consistency tolerances do not allow)."""
    if precision == "bf16":
        y = jax.lax.dot_general(
            x.astype(jnp.bfloat16), p["w"].astype(jnp.bfloat16),
            (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        y = jnp.matmul(x, p["w"], precision=jax.lax.Precision.HIGHEST)
    if "b" in p:
        y = y + p["b"]
    return y


def init_layernorm(d: int, dtype=jnp.float32) -> Params:
    return {"g": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)}


def layernorm(p: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    xhat = (x - mu) * jax.lax.rsqrt(var + eps)
    return xhat * p["g"] + p["b"]


def init_mlp(key, d_in: int, hidden: Sequence[int], d_out: int,
             dtype=jnp.float32, final_layernorm: bool = True) -> Params:
    """Paper-style MLP: hidden layers with ELU, optional output LayerNorm."""
    dims = [d_in, *hidden, d_out]
    keys = jax.random.split(key, len(dims) - 1)
    p: Params = {"layers": [init_dense(k, a, b, dtype) for k, a, b in zip(keys, dims[:-1], dims[1:])]}
    if final_layernorm:
        p["ln"] = init_layernorm(d_out, dtype)
    return p


def mlp(p: Params, x: jnp.ndarray, precision: str | None = None) -> jnp.ndarray:
    n = len(p["layers"])
    for i, lp in enumerate(p["layers"]):
        x = dense(lp, x, precision=precision)
        if i < n - 1:
            x = jax.nn.elu(x)
    if "ln" in p:
        x = layernorm(p["ln"], x)
    return x


def init_residual_mlp(key, d: int, n_hidden_layers: int, dtype=jnp.float32) -> Params:
    """Residual MLP block used by the paper's NMP layers (LayerNorm + ELU)."""
    return init_mlp(key, d, [d] * n_hidden_layers, d, dtype, final_layernorm=True)


def residual_mlp(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    return x + mlp(p, x)


# ---------------------------------------------------------------------------
# pytree math helpers
# ---------------------------------------------------------------------------

def tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


def tree_scale(a, s):
    return jax.tree.map(lambda x: x * s, a)


def tree_zeros_like(a):
    return jax.tree.map(jnp.zeros_like, a)


def global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves))


def count_params(tree) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(tree))


def cast_tree(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)
