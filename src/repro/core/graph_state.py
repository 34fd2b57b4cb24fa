"""Unified execution state for the consistent GNN: ShardedGraph + NMPPlan.

Before this module, every forward path threaded the same execution policy
by hand — ``backend=`` / ``schedule=`` / ``precision=`` / ``interpret=`` /
``block_n=`` kwargs plus an ever-growing bag of string keys in a loose
``meta`` dict — through eight files in lockstep.  The two classes here
replace that plumbing:

* :class:`ShardedGraph` — a registered pytree bundling the per-rank static
  arrays of one partition level (node/edge indices, masks, inverse
  multiplicities, halo exchange buffers, static geometric edge features,
  the per-node edge slot tables of a bounded-degree graph, the fused-kernel
  segment layouts and the interior/boundary split), with
  each coarser level of a multilevel hierarchy nested as a child
  ``ShardedGraph`` carrying its restriction/prolongation transfer maps.
  Because it is a pytree, the whole graph flows through ``jit`` /
  ``shard_map`` / ``jax.tree.map`` like any other argument; the dict keys
  live in the (hashable) treedef, so rebuilding an identically-shaped graph
  never retraces.

* :class:`NMPPlan` — a frozen, hashable execution policy: NMP backend
  (``xla`` | ``fused``), halo/compute schedule (``blocking`` | ``overlap``),
  edge-MLP matmul precision (``fp32`` | ``bf16``), Pallas interpreter flag,
  fused-kernel block sizes, and the fine + per-coarse-level
  :class:`~repro.core.halo.HaloSpec`\\ s.  Layer implementations register
  themselves per ``(backend, schedule)`` cell via :func:`register_nmp_impl`
  once, instead of being dispatched by stringly-typed kwargs at every call
  site — the next backend or schedule is a one-file registry entry.

Raw ``meta`` dicts are rejected with a ``TypeError`` wherever a
``ShardedGraph`` is expected (:func:`as_graph`), so stale callers fail
loudly instead of silently half-working.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.halo import HaloSpec, halo_spec_from_plan

# ---------------------------------------------------------------------------
# NMPPlan: frozen execution policy + the (backend, schedule) registry
# ---------------------------------------------------------------------------

XLA = "xla"
FUSED = "fused"
BLOCKING = "blocking"
OVERLAP = "overlap"
AUTO = "auto"                    # resolved to blocking|overlap by autotune()
SCHEDULES = (BLOCKING, OVERLAP, AUTO)
FP32 = "fp32"
BF16 = "bf16"
PRECISIONS = (FP32, BF16)


@dataclasses.dataclass(frozen=True)
class NMPPlan:
    """Static execution policy for every consistent-NMP forward path.

    All fields are trace-time constants: the plan is hashable and compares
    by value, so it can be closed over by ``jit`` (or passed as a static
    argument) without retracing when an equal plan is rebuilt.

    ``halo`` is the fine (level-0) exchange spec; ``coarse_halos[l-1]`` is
    level l's — each coarse level has its own ppermute rounds.  The policy
    knobs select the registered layer implementation and configure it (see
    the backend/schedule/precision taxonomy in ``repro.core.consistent_mp``).
    ``block_n`` / ``block_e`` are the fused-kernel tile sizes; they also key
    the cached segment layout ``ShardedGraph.build`` attaches.
    """
    halo: HaloSpec = HaloSpec(mode="none")
    coarse_halos: Tuple[HaloSpec, ...] = ()
    backend: str = XLA
    schedule: str = BLOCKING
    precision: str = FP32
    interpret: bool = False
    block_n: int = 128
    block_e: int = 128

    def __post_init__(self):
        if self.precision not in PRECISIONS:
            raise ValueError(f"unknown precision {self.precision!r}; "
                             f"expected one of {PRECISIONS}")
        if self.schedule not in SCHEDULES:
            raise ValueError(f"unknown schedule {self.schedule!r}; "
                             f"expected one of {SCHEDULES}")
        # the plan's interpret flag is authoritative: mirror it into every
        # halo spec so the packed exchange's Pallas pack/unpack kernels run
        # under the same interpreter policy as the fused NMP kernels
        sync = tuple(
            h if h.interpret == self.interpret
            else dataclasses.replace(h, interpret=self.interpret)
            for h in (self.halo, *self.coarse_halos))
        object.__setattr__(self, "halo", sync[0])
        object.__setattr__(self, "coarse_halos", tuple(sync[1:]))

    def replace(self, **kw) -> "NMPPlan":
        return dataclasses.replace(self, **kw)

    @property
    def seg_layout(self) -> Tuple[int, int] | None:
        """The (block_n, block_e) layout key the fused backend needs, or
        None when the xla backend makes no use of a segment layout."""
        return (self.block_n, self.block_e) if self.backend == FUSED else None

    @property
    def wants_split(self) -> bool:
        """Whether the graph must carry the interior/boundary edge split.

        ``auto`` also wants it: the graph must support whichever schedule
        the tuner picks (blocking simply ignores the split arrays).
        """
        return self.schedule in (OVERLAP, AUTO)

    @property
    def wants_packed(self) -> bool:
        """Whether the graph must carry the bucketed per-round packed halo
        arrays (``pk{k}_*``).  True for any ``HaloSpec(packed=True)`` level
        and for halo mode ``"auto"`` — the tuner's candidate set includes the
        packed neighbor format, so the graph must support it."""
        return any(h.packed or h.mode == AUTO
                   for h in (self.halo, *self.coarse_halos))

    def halos(self, n_levels: int) -> Tuple[HaloSpec, ...]:
        """Per-level exchange specs for an ``n_levels``-deep hierarchy.

        Missing coarse entries fall back to the fine spec — correct ONLY for
        the A2A / NONE modes (a NEIGHBOR fine spec with a missing coarse
        entry is rejected by ``multilevel_vcycle``, whose ``sync_fns``
        overrides are the one legitimate reason to reach that state).
        """
        return (self.halo,) + tuple(
            self.coarse_halos[i] if i < len(self.coarse_halos) else self.halo
            for i in range(n_levels - 1))

    @classmethod
    def build(cls, pg_or_hierarchy, mode: str, axis: str = "graph",
              wire_dtype=None, packed: bool = False, **policy) -> "NMPPlan":
        """Build a plan with halo specs derived from a partition's halo plan.

        ``pg_or_hierarchy`` is a ``PartitionedGraphs`` (flat model) or a
        ``MultiLevelGraphs`` (every level gets its own spec); ``mode`` is the
        exchange mode (``none`` | ``a2a`` | ``neighbor`` | ``auto`` — the
        last resolved by :meth:`autotune` over the (schedule × halo-mode ×
        wire) cross-product); ``packed=True`` selects the bucketed per-round
        wire format (neighbor only); remaining kwargs are the policy fields
        (backend/schedule/precision/...).
        """
        levels = getattr(pg_or_hierarchy, "levels", [pg_or_hierarchy])
        specs = tuple(halo_spec_from_plan(lvl.halo, mode, axis=axis,
                                          wire_dtype=wire_dtype,
                                          packed=packed)
                      for lvl in levels)
        return cls(halo=specs[0], coarse_halos=specs[1:], **policy)

    def autotune_blocks(self, hidden: int, dtype=jnp.float32) -> "NMPPlan":
        """Replace ``block_n``/``block_e`` with the static autotune table's
        choice for this model width (``repro.kernels.segment_agg.ops.
        pick_block_sizes``, keyed on hidden/dtype/platform and overridable
        via the ``REPRO_SEG_BLOCKS`` env var).  Compose with the halo
        constructors: ``NMPPlan.build(pg, mode, backend="fused")
        .autotune_blocks(cfg.hidden)``.
        """
        from repro.kernels.segment_agg.ops import pick_block_sizes
        bn, be = pick_block_sizes(hidden, dtype)
        return self.replace(block_n=bn, block_e=be)

    def autotune(self, graph, measure: bool | None = None,
                 hidden: int = 8, iters: int = 20) -> "NMPPlan":
        """Resolve ``schedule="auto"`` and/or halo mode ``"auto"``.

        Times one jitted stacked NMP layer per candidate — the (schedule ×
        halo-mode × wire) cross-product when the halo mode is ``"auto"``,
        schedules only otherwise — on ``graph`` (a stacked
        :class:`ShardedGraph`, the same proxy ``benchmarks/halo_overlap.py``
        reports) and returns a plan with the measured winner, cached per
        (graph-hash, rank-count, policy) for the process lifetime so
        repeated builds pay nothing.  ``hidden`` should match the model
        width (compute/communication balance moves the crossover).  With
        ``measure=False`` — or env var ``REPRO_SCHEDULE_AUTOTUNE=0`` — falls
        back to structural heuristics (``interior_frac`` < 0.5 -> overlap;
        halo mode -> packed neighbor).  Plans with everything fixed are
        returned unchanged.  Mirrors :meth:`autotune_blocks`.
        """
        if self.schedule != AUTO and self.halo.mode != AUTO:
            return self
        from repro.core.consistent_mp import autotune_plan
        return autotune_plan(self, graph, measure=measure,
                             hidden=hidden, iters=iters)

    def policy(self) -> dict:
        """JSON-able policy fields (no halo specs) — the plan's entry in a
        checkpoint manifest's mesh fingerprint.  An elastic resume compares
        these to decide whether the execution policy changed (allowed —
        backends/schedules are arithmetically consistent) and reuses the
        recorded resolved schedule instead of re-autotuning ``auto`` when
        the rank count is unchanged."""
        return {"backend": self.backend, "schedule": self.schedule,
                "precision": self.precision, "interpret": self.interpret,
                "block_n": self.block_n, "block_e": self.block_e,
                "halo_mode": self.halo.mode,
                "halo_packed": self.halo.packed,
                "halo_wire": (None if self.halo.wire_dtype is None
                              else jnp.dtype(self.halo.wire_dtype).name)}


_NMP_IMPLS: Dict[Tuple[str, str], Callable] = {}


def register_nmp_impl(backend: str, schedule: str):
    """Register one consistent-NMP layer implementation for a
    (backend, schedule) cell.  The registered callable has the signature

        impl(params, x, e, graph, plan, halo, sync_fn, edge_parallel_axes)
            -> (x', e')

    and is looked up once per ``nmp_layer`` call via :func:`nmp_impl` —
    adding a backend or schedule is one registration, not an eight-file
    kwarg thread.
    """
    def deco(fn):
        _NMP_IMPLS[(backend, schedule)] = fn
        return fn
    return deco


def nmp_impl(plan: NMPPlan) -> Callable:
    """Resolve the layer implementation registered for ``plan``."""
    try:
        return _NMP_IMPLS[(plan.backend, plan.schedule)]
    except KeyError:
        if plan.schedule == AUTO:
            raise ValueError(
                "schedule='auto' must be resolved before layer dispatch: "
                "call plan.autotune(graph) after ShardedGraph.build (the "
                "training loop does this for you)") from None
        known = sorted(_NMP_IMPLS)
        raise ValueError(
            f"no NMP implementation registered for backend={plan.backend!r}, "
            f"schedule={plan.schedule!r}; registered cells: {known}") from None


def registered_nmp_impls() -> Tuple[Tuple[str, str], ...]:
    return tuple(sorted(_NMP_IMPLS))


# ---------------------------------------------------------------------------
# ShardedGraph: the per-rank static arrays as one pytree
# ---------------------------------------------------------------------------

class ShardedGraph:
    """Stacked per-rank static arrays of one partition level, as a pytree.

    ``arrays`` maps name -> array with a leading rank axis (the axis the
    production mesh shards over); ``coarse`` optionally chains the next
    coarser level of a multilevel hierarchy (whose arrays additionally carry
    the ``t_fine`` / ``t_coarse`` / ``t_rw`` / ``t_pw`` transfer maps from
    this level).  Inside ``shard_map`` the same structure holds the
    rank-local slices (leading axes consumed by the sharding) — use
    :meth:`rank` to strip them explicitly.

    The array *names* live in the treedef (hashable aux data), so two graphs
    built from the same partition are trace-compatible: ``jit`` does not
    retrace across flatten/unflatten round trips or rebuilds.
    """

    __slots__ = ("arrays", "coarse")

    def __init__(self, arrays: Dict[str, jnp.ndarray],
                 coarse: "ShardedGraph | None" = None):
        if not isinstance(arrays, dict):
            raise TypeError(f"arrays must be a dict, got {type(arrays)}")
        if coarse is not None and not isinstance(coarse, ShardedGraph):
            raise TypeError("coarse must be a ShardedGraph (or None), got "
                            f"{type(coarse)}")
        self.arrays = dict(arrays)
        self.coarse = coarse

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        keys = tuple(sorted(self.arrays))
        return (tuple(self.arrays[k] for k in keys), self.coarse), keys

    @classmethod
    def tree_unflatten(cls, keys, children):
        vals, coarse = children
        obj = cls.__new__(cls)
        obj.arrays = dict(zip(keys, vals))
        obj.coarse = coarse
        return obj

    # -- mapping-style access ----------------------------------------------
    def __getitem__(self, key: str):
        try:
            return self.arrays[key]
        except KeyError:
            raise KeyError(
                f"ShardedGraph has no array {key!r} at this level; present: "
                f"{sorted(self.arrays)} — was the graph built with the plan "
                "that needs it (ShardedGraph.build(pg, coords, plan=...))?"
            ) from None

    def __contains__(self, key: str) -> bool:
        return key in self.arrays

    def keys(self):
        return self.arrays.keys()

    def items(self):
        return self.arrays.items()

    def __repr__(self) -> str:
        lv = ", ".join(f"L{i}:{len(l.arrays)} arrays"
                       for i, l in enumerate(self.levels))
        return f"ShardedGraph({lv})"

    # -- hierarchy ----------------------------------------------------------
    @property
    def levels(self) -> Tuple["ShardedGraph", ...]:
        """Fine-to-coarse chain of levels (``levels[0] is self``)."""
        out, g = [], self
        while g is not None:
            out.append(g)
            g = g.coarse
        return tuple(out)

    @property
    def n_levels(self) -> int:
        return len(self.levels)

    def level(self, lvl: int) -> "ShardedGraph":
        levels = self.levels
        if lvl >= len(levels):
            raise ValueError(
                f"multilevel graph for level {lvl} missing (graph has "
                f"{len(levels)} levels) — build the graph from the "
                "hierarchy: ShardedGraph.build(pg, coords, plan, "
                "hierarchy=...)")
        return levels[lvl]

    # -- transforms ----------------------------------------------------------
    def rank(self, r: int) -> "ShardedGraph":
        """Slice every array's leading rank axis (all levels)."""
        return jax.tree.map(lambda v: v[r], self)

    def rank_local(self) -> "ShardedGraph":
        """Strip the size-1 leading rank axis inside a shard_map body."""
        return self.rank(0)

    def with_arrays(self, **updates) -> "ShardedGraph":
        """Copy of this level with arrays added/replaced (coarse chain kept)."""
        return ShardedGraph({**self.arrays, **updates}, self.coarse)

    def specs(self, graph_axis="graph") -> "ShardedGraph":
        """Same-structure pytree of PartitionSpecs: every array sharded over
        its leading rank ax(es).  ``graph_axis`` may be one mesh axis name or
        a tuple of names (two-level spatial grids consume two leading axes).
        Feed directly to ``shard_map`` in_specs / ``NamedSharding``.
        """
        axes = (graph_axis,) if isinstance(graph_axis, str) else tuple(graph_axis)
        return jax.tree.map(
            lambda v: P(*axes, *(None,) * (v.ndim - len(axes))), self)

    # -- constructors --------------------------------------------------------
    @classmethod
    def from_arrays(cls, arrays: Dict[str, jnp.ndarray],
                    coarse: "ShardedGraph | None" = None) -> "ShardedGraph":
        """Wrap an existing name -> array mapping (adapter for callers that
        assemble their own static arrays, e.g. the sampler block metadata or
        the dry-run's ShapeDtypeStruct graphs)."""
        return cls(dict(arrays), coarse)

    @classmethod
    def build(cls, pg, coords: np.ndarray | None,
              plan: NMPPlan | None = None, hierarchy=None) -> "ShardedGraph":
        """THE constructor for real partitions (replaces the retired
        ``prepare_gnn_meta`` / ``rank_static_inputs`` /
        ``multilevel_static_inputs`` trio).

        Collects the per-rank static arrays of ``pg`` (a
        ``PartitionedGraphs``) plus the static geometric edge features from
        ``coords``; ``plan`` decides what else rides along — the fused
        backend's cached segment layout (``plan.seg_layout``) and the
        overlap schedule's interior/boundary split (``plan.wants_split``).
        The O(E log E) layout/split passes are memoized on ``pg``, so they
        run once per partition, never per step.

        ``hierarchy`` (a ``repro.core.coarsen.MultiLevelGraphs`` whose level
        0 is ``pg``) nests each coarse level as a child ShardedGraph carrying
        its transfer maps; ``coords`` must then agree with the hierarchy's
        build-time coordinates (which define every level's edge features).
        """
        plan = plan or NMPPlan()
        seg = plan.seg_layout
        split = plan.wants_split
        packed = plan.wants_packed
        if hierarchy is None:
            return cls(_level_arrays(pg, coords, seg, split, packed))
        if hierarchy.levels[0] is not pg:
            raise ValueError("hierarchy.levels[0] must be the pg passed in "
                             "(the fine partition the step fns shard over)")
        if coords is not None and coords is not hierarchy.coords[0] \
                and not np.array_equal(coords, hierarchy.coords[0]):
            raise ValueError(
                "coords disagrees with hierarchy.coords[0]: the hierarchy's "
                "build-time coordinates define every level's static edge "
                "features — rebuild the hierarchy from the transformed mesh "
                "instead of passing different coords here")
        graph = None
        for lvl in range(hierarchy.n_levels - 1, -1, -1):
            arrays = _level_arrays(hierarchy.levels[lvl], hierarchy.coords[lvl],
                                   seg, split, packed)
            if lvl >= 1:
                t = hierarchy.transfers[lvl - 1]
                arrays["t_fine"] = jnp.asarray(t.fine_idx)
                arrays["t_coarse"] = jnp.asarray(t.coarse_idx)
                arrays["t_rw"] = jnp.asarray(t.r_w)
                arrays["t_pw"] = jnp.asarray(t.p_w)
            graph = cls(arrays, graph)
        return graph


jax.tree_util.register_pytree_node_class(ShardedGraph)


def _level_arrays(pg, coords, seg_layout, split,
                  packed: bool = False) -> Dict[str, jnp.ndarray]:
    """One level's stacked static arrays: halo/edge metadata + edge geometry."""
    from repro.core.mesh_gen import edge_features as static_edge_features
    from repro.core.partition import gather_node_features

    arrays = {k: jnp.asarray(v)
              for k, v in pg.device_arrays(seg_layout=seg_layout,
                                           split=split,
                                           packed=packed).items()}
    arrays.update((k, jnp.asarray(v))
                  for k, v in (pg.slot_tables() or {}).items())
    coords_r = gather_node_features(pg, coords)
    ef = []
    for r in range(pg.R):
        e = np.stack([pg.edge_src[r], pg.edge_dst[r]], axis=-1)
        ef.append(static_edge_features(coords_r[r], e) * pg.edge_mask[r][:, None])
    arrays["static_edge_feats"] = jnp.asarray(np.stack(ef).astype(np.float32))
    return arrays


def as_graph(graph) -> ShardedGraph:
    """Validate a ShardedGraph argument; reject the retired meta-dict path
    loudly so stale callers fail with an actionable error instead of a
    shape mismatch three layers down."""
    if isinstance(graph, ShardedGraph):
        return graph
    if isinstance(graph, dict):
        raise TypeError(
            "raw meta dicts are no longer accepted by the consistent-GNN "
            "forward paths — build a ShardedGraph instead: "
            "ShardedGraph.build(pg, coords, plan, hierarchy=...) for real "
            "partitions, or ShardedGraph.from_arrays(d) to wrap an existing "
            "mapping (see CONTRIBUTING.md, 'Migrating from meta dicts')")
    raise TypeError(f"expected a ShardedGraph, got {type(graph).__name__}")
