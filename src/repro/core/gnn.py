"""The paper's encode-process-decode consistent GNN (Sec. III, Table I).

  1) node & edge encoders: local MLPs lifting F_x / F_e -> N_H;
  2) M consistent NMP layers (Sec. II-B);
  3) node decoder: local MLP N_H -> F_y (edge features discarded).

Presets: ``small()`` (N_H=8, M=4, ``mlp_hidden_layers`` 2: 3,211 params)
and ``large()`` (N_H=32, M=4, ``mlp_hidden_layers`` 5: 79,939 params), with
F_x=3 (velocity) and F_e=7 (relative velocity + distance vector +
magnitude). Each builds one hidden-to-hidden layer fewer in every MLP than
Table I: ``mlp_hidden_layers`` 3 and 6 build Table I's depth, 4,003 and
91,555 params, which are Table I's 3,979 and 91,459 with the 7 edge inputs
in place of the 4 that Table I's counts imply (3 * N_H more weights).

The forward pass names its layers on the device with ``jax.named_scope``:
``enc``, ``nmp{i}`` around each NMP layer, and ``dec`` (the vocabulary is
in ``repro.obs``).

``GNNConfig`` is pure architecture; the execution policy (backend,
schedule, precision, halo specs, ...) lives in one
:class:`~repro.core.graph_state.NMPPlan` and the static graph arrays in one
:class:`~repro.core.graph_state.ShardedGraph`.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro import nn
from repro.core.consistent_mp import init_nmp_layer, multilevel_vcycle, nmp_layer
from repro.core.graph_state import NMPPlan, as_graph


@dataclasses.dataclass(frozen=True)
class GNNConfig:
    hidden: int = 8              # N_H
    n_mp_layers: int = 4         # M
    mlp_hidden_layers: int = 2
    node_in: int = 3             # F_x (velocity)
    edge_in: int = 7             # F_e
    node_out: int = 3            # F_y
    name: str = "small"
    # --- multilevel (coarse-grid) message passing (repro.core.coarsen) ---
    n_levels: int = 1            # 1 = flat NMP; >1 adds a consistent V-cycle
    coarse_mp_layers: int = 2    # NMP layers smoothing each coarse level
    coarse_edge_in: int = 4      # coarse static edge feats (dist vec + mag)

    @staticmethod
    def small() -> "GNNConfig":
        return GNNConfig(hidden=8, n_mp_layers=4, mlp_hidden_layers=2, name="small")

    @staticmethod
    def large() -> "GNNConfig":
        return GNNConfig(hidden=32, n_mp_layers=4, mlp_hidden_layers=5, name="large")


def init_gnn(key, cfg: GNNConfig, dtype=jnp.float32) -> nn.Params:
    keys = jax.random.split(key, cfg.n_mp_layers + 3)
    params = {
        "node_enc": nn.init_mlp(keys[0], cfg.node_in, [cfg.hidden] * cfg.mlp_hidden_layers, cfg.hidden, dtype),
        "edge_enc": nn.init_mlp(keys[1], cfg.edge_in, [cfg.hidden] * cfg.mlp_hidden_layers, cfg.hidden, dtype),
        "mp": [init_nmp_layer(keys[2 + i], cfg.hidden, cfg.mlp_hidden_layers, dtype)
               for i in range(cfg.n_mp_layers)],
        "node_dec": nn.init_mlp(keys[-1], cfg.hidden, [cfg.hidden] * cfg.mlp_hidden_layers,
                                cfg.node_out, dtype, final_layernorm=False),
    }
    if cfg.n_levels > 1:
        params["coarse"] = init_coarse_levels(
            jax.random.fold_in(key, 7), cfg.hidden, cfg.mlp_hidden_layers,
            cfg.n_levels, cfg.coarse_mp_layers, cfg.coarse_edge_in, dtype)
    return params


def init_coarse_levels(key, hidden: int, mlp_hidden_layers: int,
                       n_levels: int, coarse_mp_layers: int,
                       coarse_edge_in: int, dtype=jnp.float32) -> list:
    """Per-coarse-level params for the V-cycle: an edge encoder lifting the
    level's static geometric edge features to the hidden width, plus
    ``coarse_mp_layers`` consistent NMP layers smoothing that level."""
    out = []
    for lvl in range(1, n_levels):
        kl = jax.random.fold_in(key, lvl)
        ke, *kmp = jax.random.split(kl, coarse_mp_layers + 1)
        out.append({
            "edge_enc": nn.init_mlp(ke, coarse_edge_in,
                                    [hidden] * mlp_hidden_layers, hidden, dtype),
            "mp": [init_nmp_layer(k, hidden, mlp_hidden_layers, dtype)
                   for k in kmp],
        })
    return out


def build_edge_inputs(x: jnp.ndarray, graph) -> jnp.ndarray:
    """Paper's 7-dim edge init: relative node features ++ distance vec ++ |dist|."""
    src, dst = graph["edge_src"], graph["edge_dst"]
    static_edge_feats = graph["static_edge_feats"]
    rel = jnp.take(x, dst, axis=-2) - jnp.take(x, src, axis=-2)
    if x.ndim == 3 and static_edge_feats.ndim == 2:
        static_edge_feats = jnp.broadcast_to(
            static_edge_feats[None], (x.shape[0],) + static_edge_feats.shape)
    return jnp.concatenate([rel, static_edge_feats], axis=-1)


def gnn_forward(
    params: nn.Params,
    x: jnp.ndarray,                    # [N_pad, F_x] or [B, N_pad, F_x]
    graph,                             # ShardedGraph (rank-local slice)
    plan: NMPPlan,
) -> jnp.ndarray:
    """Full encode-process-decode forward on one shard. Returns [..., N_pad, F_y].

    ``graph`` holds every static array (edge indices, masks, halo buffers,
    static geometric edge features, fused layouts, interior/boundary split,
    nested coarse levels); ``plan`` selects the NMP implementation and the
    per-level halo specs.

    When the params carry coarse levels (``GNNConfig.n_levels > 1``), the M
    fine NMP layers act as the pre-smoother and a consistent multilevel
    V-cycle runs before the decoder; ``graph`` must then carry the coarse
    chain (``ShardedGraph.build(pg, coords, plan, hierarchy=...)``).
    """
    graph = as_graph(graph)
    g0 = graph.levels[0]
    with jax.named_scope("enc"):
        e_in = build_edge_inputs(x, g0)
        h = nn.mlp(params["node_enc"], x) * g0["node_mask"][..., None]
        e = nn.mlp(params["edge_enc"], e_in) * g0["edge_mask"][..., None]
    for i, lp in enumerate(params["mp"]):
        with jax.named_scope(f"nmp{i}"):
            h, e = nmp_layer(lp, h, e, g0, plan)
    if "coarse" in params:
        h = multilevel_vcycle(params["coarse"], h, graph, plan)
    with jax.named_scope("dec"):
        y = nn.mlp(params["node_dec"], h) * g0["node_mask"][..., None]
    return y
