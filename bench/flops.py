"""Model FLOPs and parameter counts of the encode-process-decode GNN, from
the configuration's widths and the graph's node and edge counts alone.

Only the matmuls of the MLPs count (two FLOPs per multiply-add), on real
rows: padding rows and elementwise work (ELU, LayerNorm, gathers, the
aggregate) do not. A training step is taken as three forward passes (the
backward pass costs two); operations a program recomputes do not count.
"""
from __future__ import annotations


def box_graph_size(elements, order: int) -> tuple[int, int]:
    """Nodes and directed edges of the box spectral-element mesh graph: one
    lattice of ``e * order + 1`` GLL points per axis, an edge in each
    direction between neighbours along an axis."""
    pts = [e * order + 1 for e in elements]
    nodes = 1
    for n in pts:
        nodes *= n
    return nodes, 2 * sum(nodes // n * (n - 1) for n in pts)


def _mlps(model: dict) -> list[tuple[str, list[int]]]:
    """(row kind, layer dims) of every MLP, in forward order."""
    h, depth = model["hidden"], model["mlp_hidden_layers"]
    mid = [h] * depth
    out = [("node", [model["node_in"], *mid, h]),
           ("edge", [model["edge_in"], *mid, h])]
    for _ in range(model["n_mp_layers"]):
        out.append(("edge", [3 * h, *mid, h]))     # Eq. 4a on [x_i, x_j, e_ij]
        out.append(("node", [2 * h, *mid, h]))     # Eq. 4e on [a_i, x_i]
    out.append(("node", [h, *mid, model["node_out"]]))
    return out


def _has_layernorm(i: int, n: int) -> bool:
    return i < n - 1                               # every MLP but the decoder


def param_count(model: dict) -> int:
    """Weights, biases and LayerNorm scales and shifts of every MLP."""
    mlps = _mlps(model)
    total = 0
    for i, (_, dims) in enumerate(mlps):
        total += sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))
        if _has_layernorm(i, len(mlps)):
            total += 2 * dims[-1]
    return total


def forward_flops(model: dict, n_nodes: int, n_edges: int) -> int:
    """Matmul FLOPs of one forward pass over one snapshot."""
    rows = {"node": n_nodes, "edge": n_edges}
    return sum(2 * rows[kind] * sum(a * b for a, b in zip(dims[:-1], dims[1:]))
               for kind, dims in _mlps(model))


def train_step_flops(model: dict, n_nodes: int, n_edges: int,
                     batch: int = 1) -> int:
    """Forward plus backward (twice the forward) over ``batch`` snapshots."""
    return 3 * batch * forward_flops(model, n_nodes, n_edges)
