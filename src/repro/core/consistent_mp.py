"""Consistent neural message passing layer (Sec. II-B, Eq. 4a-e).

Operates on one rank's (shard's) padded arrays; the halo exchange injects the
cross-rank synchronization. With ``HaloSpec(mode='none')`` this reduces to the
standard (inconsistent) NMP layer the paper compares against; with R=1
partitioning it is the un-partitioned baseline.

Layer structure follows the paper exactly:
  4a  e_ij' = MLP_e(x_i, x_j, e_ij)            (residual MLP, LayerNorm, ELU)
  4b  a_i   = sum_{j in N(i)} e_ij' / d_ij     (segment_sum with 1/d_ij)
  4c  halo swap of local aggregates            (differentiable collective)
  4d  a_i*  = sum over coincident copies       (fused scatter-add)
  4e  x_i'  = MLP_n(a_i*, x_i)                 (residual on node features)

Every layer implementation names its parts on the device with
``jax.named_scope``: ``edge_agg`` (4a-b), ``halo`` (4c-d, with the
edge-parallel psum) and ``node`` (4e); the V-cycle names each level's
restriction, smoothing and prolongation ``vcycle/l{k}``.

Execution policy comes from one :class:`~repro.core.graph_state.NMPPlan`;
graph state from one :class:`~repro.core.graph_state.ShardedGraph`.  The
four (backend x schedule) layer implementations register themselves in the
``graph_state`` registry at import:

Backends for the 4a+4b hot loop (``plan.backend``):

* ``"xla"``   — plain lowering: HBM-materialized ``[E, 3H]`` gather+concat,
  edge MLP, then the ``segment_sum``.  Always available.  On a graph that
  carries per-node slot tables (bounded degree, see
  ``PartitionedGraphs.slot_tables``) the sum and the gathers' transposes
  are gathers through those tables; otherwise they are scatter-adds.
* ``"fused"`` — the Pallas kernel pair in ``repro.kernels.segment_agg``:
  per-tile src/dst node-id lists are scalar-prefetched into SMEM and drive
  double-buffered DMA row gathers of node features out of HBM/ANY memory;
  the full residual edge MLP (incl. LayerNorm) and the 1/d_ij-weighted
  aggregation run on the VMEM tile; a ``jax.custom_vjp`` routes the backward
  pass through a second Pallas kernel (Eq. 3 gradient consistency preserved
  — tested).  Requires the cached segment layout on the graph
  (``ShardedGraph.build`` attaches it when the plan's backend is fused).
  ``plan.interpret`` executes the same kernels through the Pallas
  interpreter so CPU CI exercises the production code path.

Both backends compute identical arithmetic (fp32-tolerance identical: only
the aggregation summation order differs), so the paper's consistency
guarantee survives the kernel swap.

Mixed precision (``plan.precision``): ``"bf16"`` runs the Eq. 4a edge-MLP
matmuls with bf16 operands and fp32 accumulation on *both* backends;
aggregation always accumulates fp32.  The default ``"fp32"`` is what the
bitwise consistency tests pin.

Schedules (``plan.schedule``):

* ``"blocking"`` — exchange and compute run serially (paper order).
* ``"overlap"``  — interior/boundary split: edges whose destination is
  shared with another rank run first, their partial aggregate enters the
  halo exchange, and the (typically much larger) interior edge set — whose
  aggregate rows the exchange never touches — is processed with no data
  dependence on the collective, so XLA's latency-hiding scheduler can run
  it under the in-flight ppermute rounds.  Arithmetically identical to
  blocking (``halo_sync(agg_bnd) + agg_int == halo_sync(agg_bnd + agg_int)``).
"""
from __future__ import annotations

import functools
from typing import Callable, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from repro import nn
from repro.core.graph_state import (
    BF16, BLOCKING, FP32, FUSED, OVERLAP, PRECISIONS, XLA, NMPPlan,
    ShardedGraph, as_graph, nmp_impl, register_nmp_impl,
)
from repro.core.halo import NEIGHBOR, HaloSpec, halo_sync
from repro.graph import segment

__all__ = [
    "XLA", "FUSED", "BLOCKING", "OVERLAP", "FP32", "BF16", "PRECISIONS",
    "init_nmp_layer", "edge_update_aggregate", "edge_update_aggregate_part",
    "node_update", "nmp_layer", "multilevel_vcycle", "restrict_aggregate",
    "prolong_aggregate", "autotune_schedule", "autotune_plan",
    "measure_plan_candidates", "interior_frac",
]


def init_nmp_layer(key, hidden: int, mlp_hidden_layers: int, dtype=jnp.float32) -> nn.Params:
    ke, kn = jax.random.split(key)
    return {
        # edge MLP consumes [x_i, x_j, e_ij] -> hidden
        "edge": nn.init_mlp(ke, 3 * hidden, [hidden] * mlp_hidden_layers, hidden, dtype),
        # node MLP consumes [a_i*, x_i] -> hidden
        "node": nn.init_mlp(kn, 2 * hidden, [hidden] * mlp_hidden_layers, hidden, dtype),
    }


def _map_batched(one, x, e):
    """Apply ``one(x_b, e_b) -> (e', agg)`` over an optional leading batch
    dim (python loop: batch sizes here are tiny and the fused kernel path
    is not vmappable)."""
    if x.ndim == 3:
        outs = [one(x[b], e[b]) for b in range(x.shape[0])]
        return jnp.stack([o[0] for o in outs]), jnp.stack([o[1] for o in outs])
    return one(x, e)


def _mlp_precision(plan: NMPPlan):
    return None if plan.precision == FP32 else plan.precision


# ---------------------------------------------------------------------------
# Eq. 4a + 4b: one aggregate implementation per backend
# ---------------------------------------------------------------------------

def _agg_xla(params, x, e, graph: ShardedGraph, plan: NMPPlan):
    src = graph["edge_src"]
    dst = graph["edge_dst"]
    n_pad = x.shape[-2]
    # a bounded-degree graph carries per-node slot tables: every gather and
    # sum below, and their transposes, are then gathers (no scatter-add)
    slots = "in_slots" in graph

    # --- Eq. 4a: edge update (residual) ---
    if slots:
        xi = segment.slot_gather(x, src, graph["out_slots"])
        xj = segment.slot_gather(x, dst, graph["in_slots"])
    else:
        xi = segment.gather(x, src)
        xj = segment.gather(x, dst)
    feats = jnp.concatenate([xi, xj, e], axis=-1)
    e_new = e + nn.mlp(params["edge"], feats, precision=_mlp_precision(plan))
    e_new = e_new * graph["edge_mask"][..., None]

    # --- Eq. 4b: local aggregation with inverse edge multiplicity ---
    weighted = e_new * graph["edge_inv_mult"][..., None]
    if slots:
        agg = segment.slot_segment_sum(weighted, dst, graph["in_slots"])
    elif x.ndim == 3:
        agg = jax.vmap(lambda w: segment.segment_sum(w, dst, n_pad))(weighted)
    else:
        agg = segment.segment_sum(weighted, dst, n_pad)
    return e_new, agg


def _agg_fused(params, x, e, graph: ShardedGraph, plan: NMPPlan):
    if "seg_perm" not in graph:
        raise ValueError(
            "backend='fused' needs the cached segment layout (seg_perm/"
            "seg_src/seg_dst) on the graph — build it with the fused plan: "
            "ShardedGraph.build(pg, coords, plan)")
    from repro.kernels.segment_agg.ops import fused_nmp_edge_agg

    def one(xb, eb):
        return fused_nmp_edge_agg(
            xb, eb, params["edge"], graph["seg_perm"], graph["seg_src"],
            graph["seg_dst"], graph["edge_mask"], graph["edge_inv_mult"],
            block_n=plan.block_n, interpret=plan.interpret,
            precision=plan.precision)

    return _map_batched(one, x, e)


def _agg_xla_part(params, x, e, graph: ShardedGraph, part: str, plan: NMPPlan):
    if f"edge_{part}_idx" not in graph:
        raise ValueError(
            "schedule='overlap' needs the interior/boundary edge split "
            f"(edge_{part}_idx) on the graph — build it with the overlap "
            "plan: ShardedGraph.build(pg, coords, plan)")
    n_pad = x.shape[-2]
    idx = graph[f"edge_{part}_idx"]         # [EP] compacted edge ids (0 pad)
    valid = graph[f"edge_{part}_valid"]     # [EP]
    src = graph["edge_src"][idx]
    dst = graph["edge_dst"][idx]
    mask = graph["edge_mask"][idx] * valid
    inv = graph["edge_inv_mult"][idx] * valid

    def one(xb, eb):
        e_sub = eb[idx]
        feats = jnp.concatenate([xb[src], xb[dst], e_sub], axis=-1)
        e_sub = (e_sub + nn.mlp(params["edge"], feats,
                                precision=_mlp_precision(plan))) \
            * mask[..., None]
        agg = segment.segment_sum(e_sub * inv[..., None], dst, n_pad)
        e_full = jnp.zeros(eb.shape[:-1] + (e_sub.shape[-1],), e_sub.dtype)
        e_full = e_full.at[idx].add(e_sub * valid[..., None])
        return e_full, agg

    return _map_batched(one, x, e)


def _agg_fused_part(params, x, e, graph: ShardedGraph, part: str, plan: NMPPlan):
    if f"seg_perm_{part}" not in graph:
        raise ValueError(
            "schedule='overlap' with backend='fused' needs the per-side "
            f"segment layout (seg_perm_{part}/seg_src_{part}/seg_dst_{part}) "
            "on the graph — build it with the fused+overlap plan: "
            "ShardedGraph.build(pg, coords, plan)")
    from repro.kernels.segment_agg.ops import fused_nmp_edge_agg

    def one(xb, eb):
        # the per-side layout holds only this side's edges, so the full
        # mask/inv-mult arrays select exactly the side's contributions
        return fused_nmp_edge_agg(
            xb, eb, params["edge"], graph[f"seg_perm_{part}"],
            graph[f"seg_src_{part}"], graph[f"seg_dst_{part}"],
            graph["edge_mask"], graph["edge_inv_mult"],
            block_n=plan.block_n, interpret=plan.interpret,
            precision=plan.precision)

    return _map_batched(one, x, e)


_AGGS = {XLA: _agg_xla, FUSED: _agg_fused}
_AGGS_PART = {XLA: _agg_xla_part, FUSED: _agg_fused_part}


def edge_update_aggregate(params, x, e, graph, plan: NMPPlan):
    """Eq. 4a + 4b on one shard: returns (e', local aggregate a).

    The rank-local part of the layer, shared by the production shard_map path
    and the stacked single-device reference — both backends are available to
    both paths, which is how backend-vs-backend consistency is tested.
    """
    graph = as_graph(graph)
    if plan.backend not in _AGGS:
        raise ValueError(f"unknown NMP backend {plan.backend!r}; "
                         f"registered: {sorted(_AGGS)}")
    return _AGGS[plan.backend](params, x, e, graph, plan)


def edge_update_aggregate_part(params, x, e, graph, part: str, plan: NMPPlan):
    """Eq. 4a + 4b restricted to one side of the interior/boundary edge split.

    Returns (e_part, agg_part), both full-size ([.., E_pad, H] / [.., N_pad,
    H]) but zero outside the side's edges / destination rows.  The two sides
    partition the real edges, so ``e_bnd + e_int`` / ``agg_bnd + agg_int``
    reproduce the unsplit ``edge_update_aggregate`` outputs; interior rows
    are disjoint from the halo send/recv rows, which is what lets the
    overlap schedule run the exchange on ``agg_bnd`` alone.
    """
    graph = as_graph(graph)
    if part not in ("bnd", "int"):
        raise ValueError(f"unknown edge split part {part!r}")
    if plan.backend not in _AGGS_PART:
        raise ValueError(f"unknown NMP backend {plan.backend!r}; "
                         f"registered: {sorted(_AGGS_PART)}")
    return _AGGS_PART[plan.backend](params, x, e, graph, part, plan)


def node_update(params: nn.Params, x: jnp.ndarray, agg: jnp.ndarray,
                graph) -> jnp.ndarray:
    """Eq. 4e: residual node MLP on [a_i*, x_i]."""
    x_new = x + nn.mlp(params["node"], jnp.concatenate([agg, x], axis=-1))
    return x_new * graph["node_mask"][..., None]


# ---------------------------------------------------------------------------
# the (backend x schedule) layer implementations — registered once
# ---------------------------------------------------------------------------

def _blocking_layer(agg_fn, params, x, e, graph, plan, halo, sync_fn,
                    edge_parallel_axes):
    """The paper's serial order: full Eq. 4a+4b, exchange, Eq. 4e."""
    with jax.named_scope("edge_agg"):
        e_new, agg = agg_fn(params, x, e, graph, plan)
    with jax.named_scope("halo"):
        if edge_parallel_axes:
            # combine partial aggregates in the activation dtype (halves
            # wire bytes when activations are bf16)
            agg = jax.lax.psum(agg.astype(e.dtype), edge_parallel_axes)
        # --- Eq. 4c + 4d: halo swap + synchronization ---
        if sync_fn is not None:
            agg = sync_fn(agg)
        else:
            agg = halo_sync(agg, graph, halo, combine="sum")
    # --- Eq. 4e: node update (residual) ---
    with jax.named_scope("node"):
        return node_update(params, x, agg, graph), e_new


def _overlap_layer(agg_part_fn, params, x, e, graph, plan, halo, sync_fn,
                   edge_parallel_axes):
    """Interior/boundary split: the exchange consumes only the boundary
    partial aggregate; interior-edge compute has no data dependence on the
    collective and overlaps the in-flight ppermute rounds."""
    # boundary side first — the exchange consumes its aggregate
    with jax.named_scope("edge_agg"):
        e_bnd, agg_bnd = agg_part_fn(params, x, e, graph, "bnd", plan)
    with jax.named_scope("halo"):
        if edge_parallel_axes:
            agg_bnd = jax.lax.psum(agg_bnd.astype(e.dtype), edge_parallel_axes)
        # --- Eq. 4c + 4d on the boundary rows only ---
        if sync_fn is not None:
            agg_sync = sync_fn(agg_bnd)
        else:
            agg_sync = halo_sync(agg_bnd, graph, halo, combine="sum")
    # interior side: independent of the collective -> overlappable
    with jax.named_scope("edge_agg"):
        e_int, agg_int = agg_part_fn(params, x, e, graph, "int", plan)
        e_new = e_bnd + e_int
    if edge_parallel_axes:
        with jax.named_scope("halo"):
            agg_int = jax.lax.psum(agg_int.astype(e.dtype), edge_parallel_axes)
    with jax.named_scope("node"):
        agg = agg_sync + agg_int          # disjoint row support
        return node_update(params, x, agg, graph), e_new


for _backend, _agg in _AGGS.items():
    register_nmp_impl(_backend, BLOCKING)(
        functools.partial(_blocking_layer, _agg))
for _backend, _agg_part in _AGGS_PART.items():
    register_nmp_impl(_backend, OVERLAP)(
        functools.partial(_overlap_layer, _agg_part))


def nmp_layer(
    params: nn.Params,
    x: jnp.ndarray,            # [N_pad, H] or [B, N_pad, H]
    e: jnp.ndarray,            # [E_pad, H] or [B, E_pad, H]
    graph,                     # ShardedGraph (rank-local or stacked slice)
    plan: NMPPlan,
    halo: HaloSpec | None = None,
    sync_fn: Callable | None = None,
    edge_parallel_axes: tuple = (),
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One consistent NMP layer. Returns (x', e').

    The implementation is resolved from the (backend, schedule) registry in
    ``repro.core.graph_state`` — see the module docstring for the taxonomy.

    ``halo`` defaults to ``plan.halo``; the multilevel V-cycle overrides it
    per level.  ``edge_parallel_axes``: second-level edge parallelism
    (beyond-paper, EXPERIMENTS §Perf): this shard holds only a slice of the
    sub-graph's edges (node set replicated across those mesh axes); the
    local aggregate is psum'ed over them before the halo sync —
    arithmetically identical to the paper's layer, the aggregation sum is
    simply split one level more.
    """
    graph = as_graph(graph)
    impl = nmp_impl(plan)
    halo = plan.halo if halo is None else halo
    return impl(params, x, e, graph, plan, halo, sync_fn, edge_parallel_axes)


# ---------------------------------------------------------------------------
# multilevel (coarse-grid) message passing
# ---------------------------------------------------------------------------

def _transfer(x: jnp.ndarray, src_idx: jnp.ndarray, dst_idx: jnp.ndarray,
              w: jnp.ndarray, n_out: int) -> jnp.ndarray:
    """Weighted gather/scatter-add: out[dst] += w * x[src] (0-weight pad)."""
    def one(xb):
        return segment.segment_sum(xb[src_idx] * w[:, None], dst_idx, n_out)
    return jax.vmap(one)(x) if x.ndim == 3 else one(x)


def restrict_aggregate(x_fine: jnp.ndarray, coarse_graph,
                       n_coarse_pad: int) -> jnp.ndarray:
    """Rank-local restriction partial sum (fine -> coarse, weight 1/|children|).

    ``coarse_graph`` is the coarse level's ShardedGraph slice, which carries
    the transfer maps from the finer level.  Each restriction edge lives on
    exactly one rank (the fine endpoint's primary), so this is a PARTIAL
    sum: the caller must complete it with ``halo_sync(..., combine='sum')``
    over the coarse level's halo plan — the same synchronization the Eq. 4b
    edge aggregate gets.  Without the halo-sum, coarse replica copies would
    hold zeros and the hierarchy would break the 1-rank == R-rank guarantee.
    """
    return _transfer(x_fine, coarse_graph["t_fine"], coarse_graph["t_coarse"],
                     coarse_graph["t_rw"], n_coarse_pad)


def prolong_aggregate(x_coarse: jnp.ndarray, coarse_graph,
                      n_fine_pad: int) -> jnp.ndarray:
    """Rank-local prolongation partial sum (coarse -> fine, weight
    1/|parents|); completed by a halo-sum over the FINE level's plan."""
    return _transfer(x_coarse, coarse_graph["t_coarse"], coarse_graph["t_fine"],
                     coarse_graph["t_pw"], n_fine_pad)


def check_coarse_halos(plan: NMPPlan, n_levels: int,
                       sync_fns: Sequence[Callable | None] | None = None):
    """NEIGHBOR-mode hierarchies need one HaloSpec per coarse level: the
    level-0 perms encode the FINE rank adjacency and cannot be reused."""
    if plan.halo.mode != NEIGHBOR:
        return
    for lvl in range(1, n_levels):
        covered = (lvl - 1 < len(plan.coarse_halos)
                   or (sync_fns is not None and sync_fns[lvl] is not None))
        if not covered:
            raise ValueError(
                "NEIGHBOR-mode multilevel exchange needs one HaloSpec "
                f"per coarse level (level {lvl} has neither a "
                f"coarse_halos entry — got {len(plan.coarse_halos)} for "
                f"{n_levels - 1} coarse levels — nor a sync_fns "
                "override): the level-0 perms encode the FINE rank "
                "adjacency and cannot be reused — build the plan via "
                "NMPPlan.build(hierarchy, mode, ...)")


def multilevel_vcycle(
    coarse_params: Sequence[nn.Params],   # one {"edge_enc", "mp"} per coarse level
    h: jnp.ndarray,                       # [N_pad, H] or [B, N_pad, H] fine state
    graph,                                # fine-level ShardedGraph w/ coarse chain
    plan: NMPPlan,
    sync_fns: Sequence[Callable | None] | None = None,
) -> jnp.ndarray:
    """One consistent V-cycle over the coarsening hierarchy. Returns h'.

    Down sweep, level l-1 -> l: the fine state is restricted
    (:func:`restrict_aggregate`), the partial sums are halo-summed over the
    coarse level's plan — the step that makes the hierarchy consistent —
    then ``coarse_params[l-1]["mp"]`` consistent NMP layers smooth at that
    level (running through the SAME (backend, schedule) registry cell as
    the fine layers: fused layouts and interior/boundary splits come from
    each level's own arrays).  Up sweep: each level's state is prolonged
    (:func:`prolong_aggregate`), halo-summed over the finer level's plan,
    and residually added.

    Per-level halo specs come from ``plan`` (``plan.halos(n_levels)``); a
    NEIGHBOR fine spec with a missing coarse entry raises rather than
    routing that level's exchange through the fine level's rank-adjacency
    perms (unless a ``sync_fns`` entry overrides that level's exchange —
    index l applies to level l, mirroring ``nmp_layer(sync_fn=...)``).
    Note a missing A2A/NONE coarse entry falls back to the fine spec,
    inheriting its ``wire_dtype`` (fine-level wire compression then also
    applies to the coarse exchanges).
    """
    graph = as_graph(graph)
    n_levels = len(coarse_params) + 1
    graph.level(n_levels - 1)          # loud error if coarse levels missing
    levels = graph.levels
    check_coarse_halos(plan, n_levels, sync_fns)
    halos = plan.halos(n_levels)

    def sync(a, lvl, g):
        if sync_fns is not None and sync_fns[lvl] is not None:
            return sync_fns[lvl](a)
        return halo_sync(a, g, halos[lvl], combine="sum")

    states = [h]
    # --- down sweep: restrict, complete partial sums, smooth ---
    for lvl in range(1, n_levels):
        with jax.named_scope(f"vcycle/l{lvl}"):
            g = levels[lvl]
            n_pad_c = g["node_mask"].shape[-1]
            c = restrict_aggregate(states[-1], g, n_pad_c)
            c = sync(c, lvl, g) * g["node_mask"][..., None]
            p = coarse_params[lvl - 1]
            e = nn.mlp(p["edge_enc"], g["static_edge_feats"]) \
                * g["edge_mask"][..., None]
            if c.ndim == 3:
                e = jnp.broadcast_to(e[None], (c.shape[0],) + e.shape)
            for lp in p["mp"]:
                c, e = nmp_layer(lp, c, e, g, plan, halo=halos[lvl],
                                 sync_fn=sync_fns[lvl] if sync_fns else None)
            states.append(c)
    # --- up sweep: prolong, complete partial sums, residual add ---
    for lvl in range(n_levels - 1, 0, -1):
        with jax.named_scope(f"vcycle/l{lvl}"):
            gf = levels[lvl - 1]
            n_pad_f = gf["node_mask"].shape[-1]
            up = prolong_aggregate(states[lvl], levels[lvl], n_pad_f)
            up = sync(up, lvl - 1, gf)
            states[lvl - 1] = (states[lvl - 1] + up) * gf["node_mask"][..., None]
    return states[0]


# ---------------------------------------------------------------------------
# measured plan autotuning (NMPPlan.autotune: schedule="auto", halo="auto")
# ---------------------------------------------------------------------------

# (graph-hash, R, policy) -> resolved pick (a schedule string for the legacy
# schedule-only path; a (schedule, halo-mode label, wire name) triple for the
# cross-product path), for the process lifetime.  One measurement per
# distinct (graph, rank-count, policy) — the same memoize-the-expensive-probe
# shape as the fused kernels' block-size autotune table.
_SCHEDULE_CACHE: dict = {}

# (graph-hash, R, policy, candidate grid) -> {(schedule, mode label, wire
# name): seconds}.  Kept separate from the pick cache so the benchmark sweep
# (benchmarks/halo_overlap.py) can read the SAME measured table the tuner
# argmins over — the "auto pick matches the best fixed config" acceptance
# check holds by construction.
_TUNE_TABLE_CACHE: dict = {}


def _graph_schedule_key(g0: dict) -> tuple:
    import hashlib
    h = hashlib.sha1()
    for k in ("edge_src", "edge_dst", "node_mask"):
        a = np.asarray(g0[k])
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return (h.hexdigest(),)


def _measure_best_schedule(plan: NMPPlan, g0: dict, hidden: int,
                           iters: int) -> str:
    """Time one jitted stacked NMP layer per schedule; return the winner.

    Uses the stacked single-device evaluator (``reference._smooth_stacked``)
    — the same proxy ``benchmarks/halo_overlap.py`` reports — with random
    params/features at the model's hidden width, min-of-``iters`` timing.
    """
    import time as _time
    from repro.core.reference import _smooth_stacked

    R, n_pad = np.asarray(g0["node_mask"]).shape
    e_pad = np.asarray(g0["edge_mask"]).shape[-1]
    params = init_nmp_layer(jax.random.PRNGKey(0), hidden, 2)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(R, n_pad, hidden)), jnp.float32)
    e = jnp.asarray(rng.normal(size=(R, e_pad, hidden)), jnp.float32)

    best, best_t = BLOCKING, float("inf")
    for sched in (BLOCKING, OVERLAP):
        cand = plan.replace(schedule=sched)
        fn = jax.jit(lambda p, xx, ee, _c=cand:
                     _smooth_stacked(p, xx, ee, g0, _c))
        jax.block_until_ready(fn(params, x, e))        # compile + warm
        t = float("inf")
        for _ in range(iters):
            t0 = _time.perf_counter()
            jax.block_until_ready(fn(params, x, e))
            t = min(t, _time.perf_counter() - t0)
        if t < best_t:
            best, best_t = sched, t
    return best


def interior_frac(g0: dict) -> float:
    """Fraction of real edges in the interior side of the split (edges whose
    aggregate rows the halo exchange never touches)."""
    if "edge_int_valid" not in g0:
        raise ValueError("graph has no interior/boundary split — build it "
                         "with a plan whose schedule is 'overlap' or 'auto'")
    n_int = float(np.asarray(g0["edge_int_valid"]).sum())
    n_bnd = float(np.asarray(g0["edge_bnd_valid"]).sum())
    return n_int / max(n_int + n_bnd, 1.0)


AUTO = "auto"

#: halo-mode labels the cross-product tuner sweeps; "neighbor-packed" is the
#: bucketed wire format (NEIGHBOR collectives over the narrow pk{k}_* arrays)
MODE_LABELS = ("a2a", "neighbor", "neighbor-packed")


def _mode_label(spec: HaloSpec) -> str:
    return f"{spec.mode}-packed" if spec.packed else spec.mode


def _wire_name(wire) -> str | None:
    return None if wire is None else jnp.dtype(wire).name


def _spec_for(spec: HaloSpec, label: str, wire_name: str | None) -> HaloSpec:
    """The fixed HaloSpec a (mode label, wire name) candidate denotes —
    perms/rounds2d/axis/interpret are kept from ``spec``."""
    import dataclasses
    if label == "neighbor-packed":
        mode, packed = NEIGHBOR, True
    elif label in ("a2a", "neighbor", "none"):
        mode, packed = label, False
    else:
        raise ValueError(f"unknown halo-mode label {label!r}; expected one "
                         f"of {MODE_LABELS}")
    wire = None if wire_name is None else jnp.dtype(wire_name)
    return dataclasses.replace(spec, mode=mode, packed=packed,
                               wire_dtype=wire)


def _resolve_plan(plan: NMPPlan, schedule: str, label: str,
                  wire_name: str | None) -> NMPPlan:
    """Apply a resolved (schedule, mode label, wire name) triple to the plan:
    the fine halo and every still-auto coarse halo (each keeps its own
    perms)."""
    halo = _spec_for(plan.halo, label, wire_name)
    coarse = tuple(_spec_for(h, label, wire_name) if h.mode == AUTO else h
                   for h in plan.coarse_halos)
    return plan.replace(schedule=schedule, halo=halo, coarse_halos=coarse)


def _packed_supported(plan: NMPPlan) -> bool:
    # the fused pack/unpack kernels need the Pallas interpreter anywhere
    # but TPU; without it the packed candidate would crash at trace time
    return plan.interpret or jax.default_backend() == "tpu"


def measure_plan_candidates(plan: NMPPlan, graph, hidden: int = 8,
                            iters: int = 20, schedules=None, modes=None,
                            wires=None) -> dict:
    """Time the (schedule × halo-mode × wire) candidate grid on the ACTUAL
    (graph, rank count), memoized for the process lifetime.

    Each candidate times one jitted stacked NMP layer
    (``reference._smooth_stacked``) with the exchange routed through the
    mode-faithful single-device emulator (``halo.halo_sync_stacked``) — the
    same per-rank arithmetic, wire masking/compression, and fused Pallas
    pack/unpack the production shard_map path runs for that candidate.

    Returns {(schedule, mode label, wire name): seconds}; ``NMPPlan.autotune``
    argmins over this table, and ``benchmarks/halo_overlap.py`` records it, so
    the auto pick matches the best measured fixed config by construction.
    """
    import itertools
    import time as _time
    from repro.core.halo import halo_sync_stacked
    from repro.core.reference import _smooth_stacked

    graph = as_graph(graph)
    g0 = graph.levels[0]
    R, n_pad = np.asarray(g0["node_mask"]).shape
    if schedules is None:
        schedules = (BLOCKING, OVERLAP) if plan.schedule == AUTO \
            else (plan.schedule,)
    if modes is None:
        modes = MODE_LABELS if _packed_supported(plan) \
            else ("a2a", "neighbor")
        if plan.halo.mode != AUTO:
            modes = (_mode_label(plan.halo),)
    if wires is None:
        wires = (None,) if plan.halo.wire_dtype is None \
            else (None, _wire_name(plan.halo.wire_dtype))
    wires = tuple(_wire_name(w) for w in wires)
    key = (_graph_schedule_key(g0), R, plan.backend, plan.precision,
           plan.interpret, tuple(schedules), tuple(modes), wires, hidden)
    cached = _TUNE_TABLE_CACHE.get(key)
    if cached is not None:
        return dict(cached)

    e_pad = np.asarray(g0["edge_mask"]).shape[-1]
    params = init_nmp_layer(jax.random.PRNGKey(0), hidden, 2)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(R, n_pad, hidden)), jnp.float32)
    e = jnp.asarray(rng.normal(size=(R, e_pad, hidden)), jnp.float32)

    table = {}
    for sched, label, wire in itertools.product(schedules, modes, wires):
        cand = plan.replace(schedule=sched,
                            halo=_spec_for(plan.halo, label, wire))
        fn = jax.jit(lambda p, xx, ee, _c=cand:
                     _smooth_stacked(p, xx, ee, g0, _c, halo_sync_stacked))
        jax.block_until_ready(fn(params, x, e))        # compile + warm
        t = float("inf")
        for _ in range(iters):
            t0 = _time.perf_counter()
            jax.block_until_ready(fn(params, x, e))
            t = min(t, _time.perf_counter() - t0)
        table[(sched, label, wire)] = t
    _TUNE_TABLE_CACHE[key] = dict(table)
    return table


def autotune_plan(plan: NMPPlan, graph, measure: bool | None = None,
                  hidden: int = 8, iters: int = 20) -> NMPPlan:
    """Resolve every ``"auto"`` field of the plan — ``schedule`` and/or the
    halo ``mode`` — against a stacked graph (see :meth:`NMPPlan.autotune`,
    the public entry point).

    Schedule-only resolution keeps the original measured probe
    (:func:`_measure_best_schedule`) and cache keys; a plan whose halo mode
    is ``"auto"`` upgrades to the (schedule × halo-mode × wire) cross-product
    measured by :func:`measure_plan_candidates`.  Wire candidates are
    ``{None, plan.halo.wire_dtype}`` — the tuner may DROP a requested lossy
    wire dtype when uncompressed measures faster, but never introduces one
    the caller didn't ask for, and never touches the wire of a fixed
    (non-auto) halo mode.
    """
    graph = as_graph(graph)
    g0 = graph.levels[0]
    nm = np.asarray(g0["node_mask"])
    if nm.ndim != 2:
        raise ValueError("autotune needs the stacked graph (leading rank "
                         f"axis); got node_mask of ndim {nm.ndim}")
    if plan.schedule != AUTO and plan.halo.mode != AUTO:
        return plan
    R = nm.shape[0]
    if R <= 1 or plan.halo.mode == "none":
        # no exchange to hide -> blocking trivially optimal; a single rank
        # needs no exchange at all
        out = plan.replace(schedule=BLOCKING) if plan.schedule == AUTO \
            else plan
        if out.halo.mode == AUTO:
            out = _resolve_plan(out, out.schedule, "none", None)
        return out
    if measure is None:
        import os
        measure = os.environ.get("REPRO_SCHEDULE_AUTOTUNE", "1") != "0"

    if plan.halo.mode != AUTO:
        # legacy schedule-only path: same probe, same cache keys
        key = (_graph_schedule_key(g0), R, plan.backend, plan.precision,
               plan.interpret, plan.halo.mode, bool(measure), hidden)
        sched = _SCHEDULE_CACHE.get(key)
        if sched is None:
            if measure:
                sched = _measure_best_schedule(plan, g0, hidden, iters)
            else:
                # structural fallback: once the exchange-independent share
                # of the edge work drops under half, there is not enough
                # interior compute to pay blocking's serialization
                sched = OVERLAP if interior_frac(g0) < 0.5 else BLOCKING
            _SCHEDULE_CACHE[key] = sched
        return plan.replace(schedule=sched)

    # cross-product path: halo mode (and possibly schedule / wire) are auto
    schedules = (BLOCKING, OVERLAP) if plan.schedule == AUTO \
        else (plan.schedule,)
    modes = MODE_LABELS if _packed_supported(plan) else ("a2a", "neighbor")
    wires = (None,) if plan.halo.wire_dtype is None \
        else (None, _wire_name(plan.halo.wire_dtype))
    key = (_graph_schedule_key(g0), R, plan.backend, plan.precision,
           plan.interpret, "cross", tuple(schedules), tuple(modes),
           tuple(wires), bool(measure), hidden)
    triple = _SCHEDULE_CACHE.get(key)
    if triple is None:
        if measure:
            table = measure_plan_candidates(plan, graph, hidden=hidden,
                                            iters=iters, schedules=schedules,
                                            modes=modes, wires=wires)
            triple = min(table, key=table.get)
        else:
            # structural fallback: neighbor rounds bound wire volume by the
            # rank degree (the paper's N-A2A insight) and the packed format
            # only narrows them further; schedule falls back as above
            if plan.schedule == AUTO:
                sched = OVERLAP if interior_frac(g0) < 0.5 else BLOCKING
            else:
                sched = plan.schedule
            label = "neighbor-packed" if _packed_supported(plan) \
                else "neighbor"
            triple = (sched, label, _wire_name(plan.halo.wire_dtype))
        _SCHEDULE_CACHE[key] = triple
    return _resolve_plan(plan, *triple)


def autotune_schedule(plan: NMPPlan, graph, measure: bool | None = None,
                      hidden: int = 8, iters: int = 20) -> NMPPlan:
    """Back-compat alias for :func:`autotune_plan` (historically the tuner
    resolved only ``schedule="auto"``; it now also resolves halo mode
    ``"auto"`` over the full candidate cross-product)."""
    return autotune_plan(plan, graph, measure=measure, hidden=hidden,
                         iters=iters)
