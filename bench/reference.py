"""Plain float32 reference of the paper's encode-process-decode GNN.

Barwey et al., arXiv:2410.01657, Sec. II-B (Eq. 4a-e) and Sec. III, on the
unpartitioned graph of a box spectral-element mesh, in straightforward
``jax.numpy`` at ``Precision.HIGHEST``. It imports nothing of the program
under test: it builds its own lattice graph, its own Taylor-Green fields,
its own seeded weights, its own loss, gradients and AdamW.

On one rank the consistent layer is the plain one: every inverse
multiplicity 1/d is 1 and the halo exchange (Eq. 4c-d) is the identity, so

  4a  e_ij' = e_ij + MLP_e([x_i, x_j, e_ij])    (i the source, j the target)
  4b  a_j   = sum over edges into j of e_ij'
  4e  x_j'  = x_j + MLP_n([a_j, x_j])

Every MLP is dense layers with ELU between them and a LayerNorm after the
last, except the decoder's. The loss is the mean squared error over nodes
and fields (Eq. 5); the target is the input snapshot (autoencoding).

Weights follow the seeded scheme the configuration states (Glorot-uniform
matrices, zero biases, unit LayerNorm scales) with its key-splitting order,
so the same seed gives the same weights without reading the program's.
"""
from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
#: a control's precision: three bf16 passes per float32 product, the
#: algorithm of ``Precision.HIGH``, written out so that it computes the same
#: on a CPU, which ignores the precision argument (on a TPU v5e the chip's
#: own ``Precision.HIGH`` rounds more than this)
BF16_3X = "bf16_3x"
LN_EPS = 1e-5


# ---------------------------------------------------------------------------
# graph and data
# ---------------------------------------------------------------------------

def gll_points(p: int) -> np.ndarray:
    """Gauss-Legendre-Lobatto nodes on [-1, 1]: the endpoints and the roots
    of the derivative of the Legendre polynomial of degree ``p``."""
    if p == 1:
        return np.array([-1.0, 1.0])
    coef = np.zeros(p + 1)
    coef[p] = 1.0
    inner = np.polynomial.legendre.legroots(np.polynomial.legendre.legder(coef))
    return np.concatenate([[-1.0], np.sort(inner), [1.0]])


def box_lattice(elements, order: int):
    """Nodes and directed edges of the box mesh with ``elements`` hexahedra
    per axis at polynomial ``order`` on the unit cube.

    The unique GLL points form one global lattice of ``e * order + 1``
    points per axis, numbered lexicographically with axis 0 slowest; every
    pair of neighbours along an axis is an edge, in both directions.
    Returns ``coords`` [N, 3] float64 and ``src``, ``dst`` [E] int32.
    """
    ref = (gll_points(order) + 1.0) / 2.0
    axes = []
    for n in elements:
        c = np.empty(n * order + 1)
        for e in range(n):
            c[e * order:(e + 1) * order + 1] = (e + ref) * (1.0 / n)
        axes.append(c)
    shape = tuple(len(a) for a in axes)
    grid = np.meshgrid(*axes, indexing="ij")
    coords = np.stack([g.reshape(-1) for g in grid], axis=-1)
    ids = np.arange(coords.shape[0]).reshape(shape)
    src, dst = [], []
    for ax in range(3):
        lo = np.take(ids, np.arange(shape[ax] - 1), axis=ax).reshape(-1)
        hi = np.take(ids, np.arange(1, shape[ax]), axis=ax).reshape(-1)
        src += [lo, hi]
        dst += [hi, lo]
    return (coords, np.concatenate(src).astype(np.int32),
            np.concatenate(dst).astype(np.int32))


def edge_geometry(coords: np.ndarray, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    """Static edge features: target minus source position, and its length."""
    rel = coords[dst] - coords[src]
    mag = np.linalg.norm(rel, axis=-1, keepdims=True)
    return np.concatenate([rel, mag], axis=-1).astype(np.float32)


def taylor_green(coords: np.ndarray, t: float, nu: float) -> np.ndarray:
    """3-D Taylor-Green vortex velocity with viscous decay exp(-2 nu (2 pi)^2 t)."""
    x = coords * (2.0 * np.pi)
    decay = np.exp(-2.0 * nu * (2.0 * np.pi) ** 2 * t)
    u = np.sin(x[:, 0]) * np.cos(x[:, 1]) * np.cos(x[:, 2])
    v = -np.cos(x[:, 0]) * np.sin(x[:, 1]) * np.cos(x[:, 2])
    return (np.stack([u, v, np.zeros_like(u)], axis=-1) * decay).astype(np.float32)


class Graph:
    """The global graph on the device: edge ends and static edge features."""

    def __init__(self, elements, order: int):
        coords, src, dst = box_lattice(elements, order)
        self.coords = coords
        self.n_nodes = coords.shape[0]
        self.n_edges = src.shape[0]
        self.arrays = {"src": jnp.asarray(src), "dst": jnp.asarray(dst),
                       "geom": jnp.asarray(edge_geometry(coords, src, dst))}


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _init_mlp(key, dims, layernorm: bool):
    keys = jax.random.split(key, len(dims) - 1)
    layers = []
    for k, a, b in zip(keys, dims[:-1], dims[1:]):
        kw, _ = jax.random.split(k)
        lim = math.sqrt(6.0 / (a + b))
        layers.append({"w": jax.random.uniform(kw, (a, b), jnp.float32, -lim, lim),
                       "b": jnp.zeros((b,), jnp.float32)})
    p = {"layers": layers}
    if layernorm:
        p["ln"] = {"g": jnp.ones((dims[-1],), jnp.float32),
                   "b": jnp.zeros((dims[-1],), jnp.float32)}
    return p


def init_params(key, model: dict):
    """Seeded weights: one key per encoder, per message-passing layer (split
    again into edge and node MLP) and for the decoder, in that order."""
    h, mid = model["hidden"], [model["hidden"]] * model["mlp_hidden_layers"]
    m = model["n_mp_layers"]
    keys = jax.random.split(key, m + 3)
    mp = []
    for i in range(m):
        ke, kn = jax.random.split(keys[2 + i])
        mp.append({"edge": _init_mlp(ke, [3 * h, *mid, h], True),
                   "node": _init_mlp(kn, [2 * h, *mid, h], True)})
    return {"node_enc": _init_mlp(keys[0], [model["node_in"], *mid, h], True),
            "edge_enc": _init_mlp(keys[1], [model["edge_in"], *mid, h], True),
            "mp": mp,
            "node_dec": _init_mlp(keys[-1], [h, *mid, model["node_out"]], False)}


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------

def _elu(x):
    # the inner where keeps expm1 finite for large x, and its gradient at
    # x = 0 at 1, the derivative of ELU from either side
    return jnp.where(x > 0, x, jnp.expm1(jnp.where(x > 0, 0.0, x)))


def _split_bf16(a):
    # reduce_precision, not a round trip through bfloat16, which a TPU
    # compiler may drop as a no-op conversion pair
    hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    return hi, jax.lax.reduce_precision(a - hi, exponent_bits=8, mantissa_bits=7)


def dot(x, w, precision):
    """``x @ w`` at ``precision``: a ``jax.lax.Precision``, or ``BF16_3X``
    (hi*hi + hi*lo + lo*hi of the operands' bf16 splits, summed in float32)."""
    if precision != BF16_3X:
        return jnp.matmul(x, w, precision=precision)
    (xh, xl), (wh, wl) = _split_bf16(x), _split_bf16(w)
    return (jnp.matmul(xh, wh, precision=HIGHEST) + jnp.matmul(xh, wl, precision=HIGHEST)
            + jnp.matmul(xl, wh, precision=HIGHEST))


def mlp(p, x, precision):
    n = len(p["layers"])
    for i, layer in enumerate(p["layers"]):
        x = dot(x, layer["w"], precision) + layer["b"]
        if i < n - 1:
            x = _elu(x)
    if "ln" in p:
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        x = (x - mu) / jnp.sqrt(var + LN_EPS) * p["ln"]["g"] + p["ln"]["b"]
    return x


def forward(params, x, graph: dict, precision=HIGHEST, remat: bool = False):
    """Predicted fields [N, 3] of one snapshot ``x`` [N, 3]."""
    src, dst = graph["src"], graph["dst"]
    e = mlp(params["edge_enc"],
            jnp.concatenate([x[dst] - x[src], graph["geom"]], axis=-1), precision)
    h = mlp(params["node_enc"], x, precision)

    def layer(lp, h, e):
        e = e + mlp(lp["edge"], jnp.concatenate([h[src], h[dst], e], axis=-1),
                    precision)                                     # Eq. 4a
        agg = jnp.zeros_like(h).at[dst].add(e)                     # Eq. 4b
        return h + mlp(lp["node"], jnp.concatenate([agg, h], axis=-1),
                       precision), e                               # Eq. 4e

    if remat:
        layer = jax.checkpoint(layer)
    for lp in params["mp"]:
        h, e = layer(lp, h, e)
    return mlp(params["node_dec"], h, precision)


def loss(params, xs, graph: dict, precision=HIGHEST, remat: bool = True):
    """Eq. 5: mean squared error of the autoencoded snapshots ``xs`` [B, N, 3]."""
    ys = jax.vmap(lambda x: forward(params, x, graph, precision, remat))(xs)
    return jnp.mean(jnp.square(ys - xs))


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(tree)))


def adamw_step(params, m, v, t, grads, opt: dict):
    """One AdamW step (weight decay ``opt['weight_decay']``) after clipping
    the gradient to global norm ``opt['clip_norm']``. Returns the clipped
    gradient, and the new parameters and moments."""
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(_global_norm(grads), 1e-12))
    g = jax.tree.map(lambda x: x * scale, grads)
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
    v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * jnp.square(b), v, g)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, a, b: p - opt["lr"] * (a / c1 / (jnp.sqrt(b / c2) + opt["eps"])
                                         + opt["weight_decay"] * p),
        params, m, v)
    return g, params, m, v


def make_train_step(opt: dict, precision=HIGHEST, loss_fn=loss):
    """Jitted ``(params, m, v, t, xs, graph) -> (loss, clipped grads, params,
    m, v)``: one step of the plain training loop."""
    def step(params, m, v, t, xs, graph):
        value, grads = jax.value_and_grad(loss_fn)(params, xs, graph, precision)
        g, params, m, v = adamw_step(params, m, v, t, grads, opt)
        return value, g, params, m, v
    return jax.jit(step)


def train(params, xs, graph: dict, opt: dict, precision=HIGHEST, loss_fn=loss):
    """Run one training step per batch [B, N, 3] of snapshots in ``xs``.
    Returns the losses, the first step's clipped gradient and the final
    parameters."""
    step = make_train_step(opt, precision, loss_fn)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, g1 = [], None
    for t, x in enumerate(xs, start=1):
        value, g, params, m, v = step(params, m, v, jnp.float32(t), x, graph)
        losses.append(float(value))
        g1 = g if g1 is None else g1
    return losses, g1, params
