"""Distributed mesh-based graph partitioning with halo metadata (Sec. II-A).

Two partitioners produce the same ``PartitionedGraphs`` structure:

* ``from_element_partition`` — the paper's scheme: elements of an ``SEMMesh``
  are assigned to ranks (NekRS-style slab/pencil/block decompositions); nodes
  on shared element faces become *coincident copies* on every touching rank,
  and face-lattice edges are duplicated across ranks (edge multiplicity
  d_ij > 1, undone by 1/d_ij scaling during aggregation — Eq. 4b).

* ``from_edge_partition`` — beyond-paper generalization to arbitrary graphs:
  directed edges are assigned to ranks (default: owner of the destination
  node); every endpoint gets a local copy on each rank using it. Each edge
  lives on exactly one rank (d_ij = 1) but node copies still require the
  halo aggregate-sum, so the same consistent-NMP machinery applies to any
  GNN architecture (GAT/GraphCast/NequIP/MACE configs use this path).

The halo plan supports the paper's exchange implementations:
  * A2A       — equal-size buffers to *all* ranks (paper's naive baseline);
  * NEIGHBOR  — TPU-native adaptation of the paper's N-A2A: the rank
    adjacency graph is greedily edge-colored; each color becomes one
    ``jax.lax.ppermute`` round in which disjoint rank pairs swap buffers.
    Rounds are O(max rank degree), independent of R (paper Table II).

Everything here is host-side numpy; device arrays are produced by ``pack``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

import numpy as np

from repro.core.mesh_gen import SEMMesh, undirected_to_directed


# ---------------------------------------------------------------------------
# containers
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RankGraph:
    """One rank's local sub-graph (host-side, un-padded)."""
    global_ids: np.ndarray       # [N_r] sorted unique global node ids
    edges: np.ndarray            # [E_r, 2] directed edges, local node indices
    edge_inv_mult: np.ndarray    # [E_r] 1/d_ij
    node_inv_mult: np.ndarray    # [N_r] 1/d_i

    @property
    def n_nodes(self) -> int:
        return int(self.global_ids.shape[0])

    @property
    def n_edges(self) -> int:
        return int(self.edges.shape[0])


@dataclasses.dataclass
class HaloPlan:
    """Padded, stacked halo-exchange metadata for R ranks.

    A2A arrays are [R, R, B_a2a]; NEIGHBOR arrays are [R, K, B_nbr] with
    ``perms`` holding one global permutation per round (static python data,
    consumed by ``jax.lax.ppermute``).
    """
    # equal-buffer all-to-all (paper's A2A)
    a2a_send_idx: np.ndarray     # int32 [R, R, B] local node idx to send to rank s
    a2a_send_mask: np.ndarray    # float32 [R, R, B]
    a2a_recv_idx: np.ndarray     # int32 [R, R, B] local idx receiving from rank s
    a2a_recv_mask: np.ndarray    # float32 [R, R, B]
    # neighbor rounds (TPU N-A2A): K ppermute rounds
    perms: List[List[Tuple[int, int]]]            # per round: [(src, dst), ...]
    nbr_send_idx: np.ndarray     # int32 [R, K, B2]
    nbr_send_mask: np.ndarray    # float32 [R, K, B2]
    nbr_recv_idx: np.ndarray     # int32 [R, K, B2]
    nbr_recv_mask: np.ndarray    # float32 [R, K, B2]

    @property
    def n_rounds(self) -> int:
        return len(self.perms)


@dataclasses.dataclass
class PartitionedGraphs:
    """Stacked padded per-rank arrays, ready to shard over the graph mesh axis."""
    R: int
    n_global: int                # unique global nodes (N of Eq. 5)
    global_ids: np.ndarray       # int32 [R, N_pad], -1 padding
    node_mask: np.ndarray        # float32 [R, N_pad]
    node_inv_mult: np.ndarray    # float32 [R, N_pad] (0 on padding)
    edge_src: np.ndarray         # int32 [R, E_pad] (0 on padding)
    edge_dst: np.ndarray         # int32 [R, E_pad]
    edge_mask: np.ndarray        # float32 [R, E_pad]
    edge_inv_mult: np.ndarray    # float32 [R, E_pad] (0 on padding)
    halo: HaloPlan
    # compact gather/scatter index layouts for the fused NMP kernel,
    # memoized per (block_n, block_e, part) — the host-side sort runs once
    # per partition, not once per training step
    _seg_layouts: Dict[Tuple[int, int, str], dict] = dataclasses.field(
        default_factory=dict, repr=False, compare=False)
    # interior/boundary edge classification for the overlap schedule,
    # memoized (host-side, one pass per partition)
    _int_split: dict | None = dataclasses.field(
        default=None, repr=False, compare=False)
    # bucketed per-round packed halo arrays, memoized per bucket size
    _packed_halos: Dict[int, dict] = dataclasses.field(
        default_factory=dict, repr=False, compare=False)
    # per-node edge slot tables for the gather-only Eq. 4a-b, memoized
    # ({} once the rank graphs are found not bounded-degree)
    _slots: dict | None = dataclasses.field(
        default=None, repr=False, compare=False)

    @property
    def n_pad(self) -> int:
        return int(self.global_ids.shape[1])

    @property
    def e_pad(self) -> int:
        return int(self.edge_src.shape[1])

    def interior_split(self) -> dict:
        """Cached interior/boundary classification (overlap-schedule support).

        A node is *boundary* when a coincident copy lives on another rank
        (it appears in some halo send buffer); an edge is *boundary* when its
        destination is a boundary node — its aggregate contribution feeds the
        halo exchange. Interior edges land only on rows the exchange never
        reads or writes, which is what makes the overlap schedule
        arithmetically identical to the blocking one
        (``halo_sync(agg_bnd) + agg_int == halo_sync(agg_bnd + agg_int)``).

        Returns stacked [R, ...] arrays:
          node_bnd_mask  [R, N_pad]  1.0 on boundary nodes;
          edge_bnd_mask / edge_int_mask [R, E_pad] disjoint split of
            edge_mask;
          edge_bnd_idx / edge_int_idx [R, EB] / [R, EI] compacted edge-id
            lists (0 on padding) with edge_bnd_valid / edge_int_valid masks —
            the xla backend gathers each sub-problem through these;
          interior_frac  fraction of real edges that are interior (the share
            of Eq. 4a+4b work overlappable with the exchange).
        """
        if self._int_split is not None:
            return self._int_split
        h = self.halo
        node_bnd = np.zeros((self.R, self.n_pad), dtype=np.float32)
        for r in range(self.R):
            sent = h.a2a_send_idx[r][h.a2a_send_mask[r] > 0]
            node_bnd[r, sent] = 1.0
        node_bnd *= self.node_mask
        edge_bnd = np.take_along_axis(node_bnd, self.edge_dst, axis=1) \
            * self.edge_mask
        edge_int = self.edge_mask - edge_bnd

        def compact(mask):
            ids = [np.nonzero(mask[r] > 0)[0] for r in range(self.R)]
            width = _round_up(max((i.size for i in ids), default=1), 8)
            idx = np.zeros((self.R, width), dtype=np.int32)
            valid = np.zeros((self.R, width), dtype=np.float32)
            for r, i in enumerate(ids):
                idx[r, :i.size] = i
                valid[r, :i.size] = 1.0
            return idx, valid

        bnd_idx, bnd_valid = compact(edge_bnd)
        int_idx, int_valid = compact(edge_int)
        n_real = float(self.edge_mask.sum())
        self._int_split = dict(
            node_bnd_mask=node_bnd,
            edge_bnd_mask=edge_bnd, edge_int_mask=edge_int,
            edge_bnd_idx=bnd_idx, edge_bnd_valid=bnd_valid,
            edge_int_idx=int_idx, edge_int_valid=int_valid,
            interior_frac=float(edge_int.sum()) / n_real if n_real else 0.0,
        )
        return self._int_split

    def segment_layout(self, block_n: int, block_e: int,
                       part: str = "all") -> dict:
        """Cached compact gather/scatter index layout for the fused
        segment-agg kernel (scalar-prefetch DMA gathers).

        Runs ``compact_gather_layout`` once per rank (padding edges are
        routed to an out-of-range sentinel so they are dropped from the
        tiles) and pads the per-rank tile counts to a common maximum so the
        stacked arrays shard over the rank axis — the pad tiles are entirely
        empty (``perm == -1``, src/dst 0) and weight-masked inside the
        kernel. Unlike the old dst-aligned block layout there is no
        per-node-block padding: tile occupancy is E / (T·BE) by
        construction, so no waste metric is recorded.

        ``part`` restricts the layout to one side of the interior/boundary
        split (``"int"`` | ``"bnd"``, see :meth:`interior_split`) — the
        overlap schedule runs the fused kernel once per side, so each side's
        layout must drop the other side's edges.

        ``block_n`` does not shape the compact layout (node rows are
        DMA-gathered individually) but stays in the cache key so callers
        that thread (block_n, block_e) uniformly keep exact memoization.

        Returns {perm [R, T, BE] int32 (-1 = empty slot), src [R, T, BE]
                 int32, dst [R, T, BE] int32, n_tiles, block_n, block_e}.
        """
        key = (int(block_n), int(block_e), part)
        cached = self._seg_layouts.get(key)
        if cached is not None:
            return cached
        from repro.kernels.segment_agg.ops import compact_gather_layout
        if part == "all":
            keep = self.edge_mask
        elif part in ("int", "bnd"):
            keep = self.interior_split()[f"edge_{part}_mask"]
        else:
            raise ValueError(f"unknown layout part {part!r}")
        per_rank = []
        for r in range(self.R):
            # excluded edges get dst = n_pad -> dropped by the layout pass
            dst = np.where(keep[r] > 0, self.edge_dst[r], self.n_pad)
            per_rank.append(compact_gather_layout(
                self.edge_src[r], dst, self.n_pad, block_e))
        nt = max(l["n_tiles"] for l in per_rank)
        perm = np.full((self.R, nt, block_e), -1, dtype=np.int32)
        src = np.zeros((self.R, nt, block_e), dtype=np.int32)
        dst_t = np.zeros((self.R, nt, block_e), dtype=np.int32)
        for r, l in enumerate(per_rank):
            perm[r, :l["n_tiles"]] = l["perm"]
            src[r, :l["n_tiles"]] = l["src"]
            dst_t[r, :l["n_tiles"]] = l["dst"]
        layout = dict(perm=perm, src=src, dst=dst_t, n_tiles=nt,
                      block_n=int(block_n), block_e=int(block_e))
        self._seg_layouts[key] = layout
        return layout

    def slot_tables(self) -> Dict[str, np.ndarray] | None:
        """Cached per-node edge slot tables: the inverse maps of
        ``edge_dst`` / ``edge_src`` that let Eq. 4a-b sum and gather with no
        scatter (``repro.graph.segment.slot_segment_sum``).

        ``in_slots`` [R, N_pad, D] int32 lists, for each local node, the ids
        of the real edges whose dst is that node, in edge order;
        ``out_slots`` the same by src.  ``D`` is the largest in- or
        out-degree over ranks; padding slots hold ``E_pad``, an id that reads
        zero.  Edges with ``edge_mask`` 0 are left out of both tables.

        None where the rank graphs have no real edge, or are not of bounded
        degree, i.e. the tables would hold more than ``SLOT_FILL`` slots per
        padded edge (``N_pad * D > SLOT_FILL * E_pad``): a hub would make
        every row as wide as its degree.
        """
        if self._slots is None:
            keep = self.edge_mask > 0
            counts = [_degrees(ids, keep, self.n_pad)
                      for ids in (self.edge_dst, self.edge_src)]
            width = max(int(c.max(initial=0)) for c in counts)
            self._slots = {}
            if 0 < width and self.n_pad * width <= SLOT_FILL * self.e_pad:
                for name, ids, c in zip(("in_slots", "out_slots"),
                                        (self.edge_dst, self.edge_src), counts):
                    self._slots[name] = _slot_table(ids, keep, c, width,
                                                    self.e_pad)
        return self._slots or None

    def packed_halo(self, bucket: int = 8) -> Dict[str, np.ndarray]:
        """Cached bucketed per-round packed halo arrays (the packed wire
        format — see :func:`packed_halo_arrays`).  One dict entry set per
        NEIGHBOR round ``k``: ``pk{k}_send_idx / _send_mask / _recv_idx /
        _recv_mask`` of per-round width ``w_k`` (max real entries over ranks
        in that round, rounded up to ``bucket``) instead of the dense global
        max ``B``."""
        key = int(bucket)
        cached = self._packed_halos.get(key)
        if cached is None:
            h = self.halo
            cached = packed_halo_arrays(dict(
                nbr_send_idx=h.nbr_send_idx, nbr_send_mask=h.nbr_send_mask,
                nbr_recv_idx=h.nbr_recv_idx, nbr_recv_mask=h.nbr_recv_mask,
            ), bucket=bucket)
            self._packed_halos[key] = cached
        return cached

    def wire_bytes(self, mode: str, packed: bool = False, feat_dim: int = 1,
                   wire_dtype=None, bucket: int = 8) -> dict:
        """Per-rank on-wire halo payload for ONE exchange of a
        ``[N, feat_dim]`` aggregate (``partition_quality``-style metric).

        * ``mode="a2a"``: every rank ships its full dense buffer to each of
          the other R-1 ranks — ``(R-1) * B * feat_dim`` elements regardless
          of how many of them are masked padding.
        * ``mode="neighbor"``: a rank ships one ``B``-wide buffer per round
          it participates in (K = max rank degree rounds total).
        * ``packed=True`` (neighbor only): the round-``k`` buffer is the
          bucketed width ``w_k`` instead of the dense global max ``B``.

        Returns ``{mode, packed, itemsize, per_rank, max, mean, total}``
        (bytes; ``per_rank`` is a plain list for JSON).
        """
        if mode not in ("a2a", "neighbor"):
            raise ValueError(f"wire_bytes: unknown halo mode {mode!r}")
        if packed and mode == "a2a":
            raise ValueError(
                "wire_bytes: packed buffers are neighbor-only — a2a "
                "(jax.lax.all_to_all) requires uniform per-rank buffers")
        itemsize = int(np.dtype(np.float32 if wire_dtype is None
                                else wire_dtype).itemsize)
        h = self.halo
        per_rank = np.zeros(self.R, dtype=np.int64)
        if mode == "a2a":
            B = h.a2a_send_idx.shape[-1]
            per_rank[:] = (self.R - 1) * B * feat_dim * itemsize
        else:
            K, B = h.nbr_send_idx.shape[1], h.nbr_send_idx.shape[2]
            pk = self.packed_halo(bucket) if packed else None
            for k in range(K):
                width = pk[f"pk{k}_send_idx"].shape[-1] if packed else B
                participates = (h.nbr_send_mask[:, k].sum(axis=-1) > 0) \
                    | (h.nbr_recv_mask[:, k].sum(axis=-1) > 0)
                per_rank += participates * width * feat_dim * itemsize
        return dict(mode=mode, packed=bool(packed), itemsize=itemsize,
                    per_rank=[int(v) for v in per_rank],
                    max=int(per_rank.max()) if self.R else 0,
                    mean=float(per_rank.mean()) if self.R else 0.0,
                    total=int(per_rank.sum()))

    def device_arrays(self, seg_layout: Tuple[int, int] | None = None,
                      split: bool = False,
                      packed: bool = False) -> Dict[str, np.ndarray]:
        """The dict of arrays a train/serve step consumes (shard over axis 0).

        ``seg_layout=(block_n, block_e)`` additionally includes the cached
        compact gather/scatter index lists (``seg_perm``/``seg_src``/
        ``seg_dst``) the fused NMP backend's scalar-prefetch DMA kernels
        consume.

        ``split=True`` attaches the interior/boundary edge split
        (:meth:`interior_split`) consumed by the overlap-schedule NMP
        implementations (``NMPPlan(schedule="overlap")``)
        — the compacted ``edge_{bnd,int}_idx``/``_valid`` index lists for the
        xla backend and, when ``seg_layout`` is also given, the per-side
        fused layouts ``seg_{perm,src,dst}_{bnd,int}``.

        ``packed=True`` attaches the bucketed per-round packed halo arrays
        (:meth:`packed_halo`) consumed by ``HaloSpec(packed=True)`` and the
        halo-mode autotuner.
        """
        h = self.halo
        out = dict(
            node_mask=self.node_mask, node_inv_mult=self.node_inv_mult,
            edge_src=self.edge_src, edge_dst=self.edge_dst,
            edge_mask=self.edge_mask, edge_inv_mult=self.edge_inv_mult,
            a2a_send_idx=h.a2a_send_idx, a2a_send_mask=h.a2a_send_mask,
            a2a_recv_idx=h.a2a_recv_idx, a2a_recv_mask=h.a2a_recv_mask,
            nbr_send_idx=h.nbr_send_idx, nbr_send_mask=h.nbr_send_mask,
            nbr_recv_idx=h.nbr_recv_idx, nbr_recv_mask=h.nbr_recv_mask,
        )
        if seg_layout is not None:
            layout = self.segment_layout(*seg_layout)
            out["seg_perm"] = layout["perm"]
            out["seg_src"] = layout["src"]
            out["seg_dst"] = layout["dst"]
        if split:
            sp = self.interior_split()
            for k in ("edge_bnd_idx", "edge_bnd_valid",
                      "edge_int_idx", "edge_int_valid"):
                out[k] = sp[k]
            if seg_layout is not None:
                for part in ("bnd", "int"):
                    lay = self.segment_layout(*seg_layout, part=part)
                    out[f"seg_perm_{part}"] = lay["perm"]
                    out[f"seg_src_{part}"] = lay["src"]
                    out[f"seg_dst_{part}"] = lay["dst"]
        if packed:
            out.update(self.packed_halo())
        return out


# ---------------------------------------------------------------------------
# element partitioning (NekRS-style decompositions)
# ---------------------------------------------------------------------------

#: most slots per padded edge for which a rank graph gets slot tables
SLOT_FILL = 1.25


def _degrees(ids: np.ndarray, keep: np.ndarray, n_pad: int) -> np.ndarray:
    """[R * n_pad] count of kept edges per (rank, node id)."""
    r, e = np.nonzero(keep)
    return np.bincount(r * n_pad + ids[r, e], minlength=ids.shape[0] * n_pad)


def _slot_table(ids: np.ndarray, keep: np.ndarray, counts: np.ndarray,
                width: int, e_pad: int) -> np.ndarray:
    """[R, n_pad, width] ids of the kept edges of each (rank, node id), in
    edge order, padded with ``e_pad``."""
    R = ids.shape[0]
    n_pad = counts.size // R
    r, e = np.nonzero(keep)                  # rank-major, edge order
    key = r * n_pad + ids[r, e]
    order = np.argsort(key, kind="stable")
    key = key[order]
    start = np.cumsum(counts) - counts
    table = np.full((counts.size, width), e_pad, dtype=np.int32)
    table[key, np.arange(key.size) - start[key]] = e[order]
    return table.reshape(R, n_pad, width)


def partition_elements(mesh: SEMMesh, rank_grid: Sequence[int]) -> np.ndarray:
    """Assign elements to ranks by blocks of the element grid.

    ``rank_grid`` has one entry per axis; (R,1,1) = slabs, (a,b,1) = pencils,
    (a,b,c) = sub-cubes (the decompositions discussed around Table II).
    """
    if len(rank_grid) != mesh.dim:
        raise ValueError("rank_grid must match mesh dim")
    for n, r in zip(mesh.nelem_axes, rank_grid):
        if n % r != 0:
            raise ValueError(f"elements per axis {n} not divisible by ranks {r}")
    blocks = [n // r for n, r in zip(mesh.nelem_axes, rank_grid)]
    e2r = np.empty(mesh.n_elem, dtype=np.int64)
    for e in range(mesh.n_elem):
        gidx = mesh.element_grid_index(e)
        ridx = [g // b for g, b in zip(gidx, blocks)]
        rank = 0
        for ax in range(mesh.dim - 1, -1, -1):
            rank = rank * rank_grid[ax] + ridx[ax]
        e2r[e] = rank
    return e2r


def from_element_partition(mesh: SEMMesh, elem2rank: np.ndarray, R: int) -> List[RankGraph]:
    """Build per-rank reduced local graphs (Fig. 3c) from an element partition."""
    # per-element undirected edge list (same generator, but per rank subset)
    from repro.core.mesh_gen import element_lattice_edges
    le = element_lattice_edges(mesh.p, mesh.dim)

    node_mult = np.zeros(mesh.n_nodes, dtype=np.int64)
    # edge multiplicity: count ranks owning each undirected global edge
    edge_key_mult: Dict[Tuple[int, int], int] = {}
    rank_nodes: List[np.ndarray] = []
    rank_und_edges: List[np.ndarray] = []

    for r in range(R):
        elems = np.nonzero(elem2rank == r)[0]
        if elems.size == 0:
            rank_nodes.append(np.zeros(0, dtype=np.int64))
            rank_und_edges.append(np.zeros((0, 2), dtype=np.int64))
            continue
        en = mesh.elem_nodes[elems]                  # [ne, npts]
        gids = np.unique(en)                         # local collapse of coincident nodes
        src = en[:, le[:, 0]].reshape(-1)
        dst = en[:, le[:, 1]].reshape(-1)
        lo, hi = np.minimum(src, dst), np.maximum(src, dst)
        pairs = np.unique(np.stack([lo, hi], axis=-1), axis=0)  # local dedup
        rank_nodes.append(gids)
        rank_und_edges.append(pairs)
        node_mult[gids] += 1
        for a, b in pairs:
            edge_key_mult[(int(a), int(b))] = edge_key_mult.get((int(a), int(b)), 0) + 1

    graphs: List[RankGraph] = []
    for r in range(R):
        gids = rank_nodes[r]
        g2l = {int(g): i for i, g in enumerate(gids)}
        und_r = rank_und_edges[r]
        dir_r = undirected_to_directed(und_r) if und_r.size else np.zeros((0, 2), dtype=np.int64)
        loc = np.array([[g2l[int(a)], g2l[int(b)]] for a, b in dir_r], dtype=np.int64).reshape(-1, 2)
        inv_mult = np.array(
            [1.0 / edge_key_mult[(min(int(a), int(b)), max(int(a), int(b)))] for a, b in dir_r],
            dtype=np.float32,
        ).reshape(-1)
        graphs.append(RankGraph(
            global_ids=gids,
            edges=loc,
            edge_inv_mult=inv_mult,
            node_inv_mult=(1.0 / node_mult[gids]).astype(np.float32),
        ))
    return graphs


# ---------------------------------------------------------------------------
# generic edge partitioning for arbitrary graphs (beyond-paper)
# ---------------------------------------------------------------------------

def from_edge_partition(
    n_nodes: int,
    directed_edges: np.ndarray,
    R: int,
    node2part: np.ndarray | None = None,
    assign: str = "dst",
    extra_nodes: Sequence[np.ndarray] | None = None,
) -> List[RankGraph]:
    """Vertex-cut partition of an arbitrary directed edge list.

    Every node's *primary* copy lives on ``node2part[node]`` (contiguous
    blocks by default); each directed edge is assigned to one rank
    (``assign`` = 'dst' | 'src'); endpoint copies are replicated wherever
    used. d_ij == 1 always; d_i = number of ranks holding a copy of i.

    ``extra_nodes`` (one array of global ids per rank) forces additional
    replica copies beyond the edge-endpoint closure — the multilevel
    hierarchy uses this to place a coarse-node copy on every rank that owns
    restriction/prolongation edges into it (``repro.core.coarsen``), so the
    inter-level transfer aggregates can be completed by the same halo-sum
    machinery as the edge aggregates.
    """
    if node2part is None:
        node2part = (np.arange(n_nodes) * R) // max(n_nodes, 1)
    node2part = node2part.astype(np.int64)
    e_owner = node2part[directed_edges[:, 1 if assign == "dst" else 0]]

    node_mult = np.zeros(n_nodes, dtype=np.int64)
    rank_nodes: List[np.ndarray] = []
    rank_edges: List[np.ndarray] = []
    for r in range(R):
        er = directed_edges[e_owner == r]
        prim = np.nonzero(node2part == r)[0]
        parts = [er.reshape(-1), prim]
        if extra_nodes is not None and len(extra_nodes[r]):
            parts.append(np.asarray(extra_nodes[r], dtype=np.int64))
        gids = np.unique(np.concatenate(parts))
        rank_nodes.append(gids)
        rank_edges.append(er)
        node_mult[gids] += 1

    graphs: List[RankGraph] = []
    for r in range(R):
        gids = rank_nodes[r]
        lookup = np.full(n_nodes, -1, dtype=np.int64)
        lookup[gids] = np.arange(gids.size)
        er = rank_edges[r]
        loc = lookup[er].reshape(-1, 2) if er.size else np.zeros((0, 2), dtype=np.int64)
        graphs.append(RankGraph(
            global_ids=gids,
            edges=loc,
            edge_inv_mult=np.ones(loc.shape[0], dtype=np.float32),
            node_inv_mult=(1.0 / node_mult[gids]).astype(np.float32),
        ))
    return graphs


# ---------------------------------------------------------------------------
# halo plan construction
# ---------------------------------------------------------------------------

def _round_up(x: int, m: int) -> int:
    return ((max(x, 1) + m - 1) // m) * m


def greedy_edge_coloring(pairs: List[Tuple[int, int]]) -> List[List[Tuple[int, int]]]:
    """Color rank-pair edges so same-color pairs are disjoint (<= Δ+1 colors).

    Pairs are processed largest-degree-endpoints first for tighter colorings.
    Returns rounds: list of lists of (r, s) with r < s.
    """
    deg: Dict[int, int] = {}
    for a, b in pairs:
        deg[a] = deg.get(a, 0) + 1
        deg[b] = deg.get(b, 0) + 1
    order = sorted(pairs, key=lambda p: -(deg[p[0]] + deg[p[1]]))
    used: Dict[int, set] = {}
    rounds: List[List[Tuple[int, int]]] = []
    for a, b in order:
        c = 0
        while c in used.get(a, set()) or c in used.get(b, set()):
            c += 1
        while len(rounds) <= c:
            rounds.append([])
        rounds[c].append((a, b))
        used.setdefault(a, set()).add(c)
        used.setdefault(b, set()).add(c)
    return rounds


def build_halo_plan(graphs: List[RankGraph], pad_to: int = 8) -> HaloPlan:
    """Shared-node send/recv masks for both exchange modes.

    For each rank pair (r, s) with shared global ids, both directions exchange
    the local aggregates at those ids, sorted by global id (fixing summation
    order => deterministic results).
    """
    R = len(graphs)
    g2l = []
    for g in graphs:
        d = {int(gid): i for i, gid in enumerate(g.global_ids)}
        g2l.append(d)

    shared: Dict[Tuple[int, int], np.ndarray] = {}
    for r in range(R):
        for s in range(r + 1, R):
            common = np.intersect1d(graphs[r].global_ids, graphs[s].global_ids, assume_unique=True)
            if common.size:
                shared[(r, s)] = common  # sorted

    # ---- A2A equal buffers (paper baseline) ----
    B = _round_up(max((v.size for v in shared.values()), default=1), pad_to)
    a2a_send_idx = np.zeros((R, R, B), dtype=np.int32)
    a2a_send_mask = np.zeros((R, R, B), dtype=np.float32)
    a2a_recv_idx = np.zeros((R, R, B), dtype=np.int32)
    a2a_recv_mask = np.zeros((R, R, B), dtype=np.float32)
    for (r, s), common in shared.items():
        n = common.size
        lr = np.array([g2l[r][int(g)] for g in common], dtype=np.int32)
        ls = np.array([g2l[s][int(g)] for g in common], dtype=np.int32)
        # r -> s
        a2a_send_idx[r, s, :n] = lr
        a2a_send_mask[r, s, :n] = 1.0
        a2a_recv_idx[s, r, :n] = ls
        a2a_recv_mask[s, r, :n] = 1.0
        # s -> r
        a2a_send_idx[s, r, :n] = ls
        a2a_send_mask[s, r, :n] = 1.0
        a2a_recv_idx[r, s, :n] = lr
        a2a_recv_mask[r, s, :n] = 1.0

    # ---- NEIGHBOR ppermute rounds ----
    rounds = greedy_edge_coloring(list(shared.keys())) if shared else []
    K = max(len(rounds), 1)
    B2 = B
    nbr_send_idx = np.zeros((R, K, B2), dtype=np.int32)
    nbr_send_mask = np.zeros((R, K, B2), dtype=np.float32)
    nbr_recv_idx = np.zeros((R, K, B2), dtype=np.int32)
    nbr_recv_mask = np.zeros((R, K, B2), dtype=np.float32)
    perms: List[List[Tuple[int, int]]] = []
    for k, rnd in enumerate(rounds or [[]]):
        perm: List[Tuple[int, int]] = []
        for (r, s) in rnd:
            common = shared[(r, s)]
            n = common.size
            lr = np.array([g2l[r][int(g)] for g in common], dtype=np.int32)
            ls = np.array([g2l[s][int(g)] for g in common], dtype=np.int32)
            nbr_send_idx[r, k, :n] = lr
            nbr_send_mask[r, k, :n] = 1.0
            nbr_recv_idx[r, k, :n] = lr
            nbr_recv_mask[r, k, :n] = 1.0
            nbr_send_idx[s, k, :n] = ls
            nbr_send_mask[s, k, :n] = 1.0
            nbr_recv_idx[s, k, :n] = ls
            nbr_recv_mask[s, k, :n] = 1.0
            perm.append((r, s))
            perm.append((s, r))
        perms.append(perm)
    return HaloPlan(
        a2a_send_idx=a2a_send_idx, a2a_send_mask=a2a_send_mask,
        a2a_recv_idx=a2a_recv_idx, a2a_recv_mask=a2a_recv_mask,
        perms=perms,
        nbr_send_idx=nbr_send_idx, nbr_send_mask=nbr_send_mask,
        nbr_recv_idx=nbr_recv_idx, nbr_recv_mask=nbr_recv_mask,
    )


def packed_halo_arrays(nbr: Dict[str, np.ndarray],
                       bucket: int = 8) -> Dict[str, np.ndarray]:
    """Bucketed per-round truncation of dense NEIGHBOR halo arrays.

    The dense ``nbr_*`` arrays are ``[R, K, B]`` with ``B`` the GLOBAL max
    shared-boundary size over all rank pairs — at realistic rank counts most
    of every round's buffer is masked padding.  Because the plan builders
    prefix-pack real entries (mask is a 1.0-prefix), truncating round ``k``
    to ``w_k = round_up(max real entries over ranks, bucket)`` keeps every
    real entry: the packed arrays are pure slices of the dense ones, which
    is what makes the packed wire format bitwise-identical in value.

    Works on both :func:`build_halo_plan` NEIGHBOR arrays and
    :func:`build_2d_halo_rounds` arrays.  Returns one rectangular array set
    per round (``pk{k}_send_idx`` [R, w_k], ...), so each can live in a
    ``ShardedGraph`` and shard over the rank axis.
    """
    send_mask, recv_mask = nbr["nbr_send_mask"], nbr["nbr_recv_mask"]
    R, K, B = send_mask.shape
    out: Dict[str, np.ndarray] = {}
    for k in range(K):
        occ = max(int((send_mask[:, k] > 0).sum(axis=-1).max(initial=0)),
                  int((recv_mask[:, k] > 0).sum(axis=-1).max(initial=0)))
        w = min(_round_up(occ, bucket), B)
        # the truncation must drop only padding (prefix-packed invariant)
        if float(send_mask[:, k, w:].sum()) or float(recv_mask[:, k, w:].sum()):
            raise ValueError(
                f"packed_halo_arrays: round {k} has real entries beyond "
                f"width {w} — halo arrays are not prefix-packed")
        for name in ("send_idx", "send_mask", "recv_idx", "recv_mask"):
            out[f"pk{k}_{name}"] = np.ascontiguousarray(
                nbr[f"nbr_{name}"][:, k, :w])
    return out


def flat_rounds2d_perms(grid: Tuple[int, int]) -> Tuple[Tuple[Tuple[int, int], ...], ...]:
    """Flat per-round (src, dst) rank pairs for :func:`build_2d_halo_rounds`.

    Each rounds2d round routes one uniform (da, db) torus shift as <=2
    chained per-axis ppermute hops; their composition delivers rank
    ``a*Gb + b``'s buffer to ``(a+da)*Gb + (b+db)`` exactly when that rank
    exists (partial chains deliver zeros, which the recv mask drops).  The
    single-device emulator (``halo_sync_stacked``) uses these flat pairs in
    place of the per-axis collectives; the shift order here mirrors
    ``build_2d_halo_rounds`` and must stay in sync with it.
    """
    Ga, Gb = grid
    shifts = [(da, db) for da in (-1, 0, 1) for db in (-1, 0, 1)
              if not (da == 0 and db == 0)]
    rounds = []
    for da, db in shifts:
        perm = []
        for a in range(Ga):
            for b in range(Gb):
                a2, b2 = a + da, b + db
                if 0 <= a2 < Ga and 0 <= b2 < Gb:
                    perm.append((a * Gb + b, a2 * Gb + b2))
        rounds.append(tuple(perm))
    return tuple(rounds)


def pack(graphs: List[RankGraph], n_global: int, pad_to: int = 8) -> PartitionedGraphs:
    """Pad per-rank graphs to common shapes and stack along axis 0."""
    R = len(graphs)
    n_pad = _round_up(max(g.n_nodes for g in graphs), pad_to)
    e_pad = _round_up(max(g.n_edges for g in graphs), pad_to)
    gid = np.full((R, n_pad), -1, dtype=np.int32)
    nmask = np.zeros((R, n_pad), dtype=np.float32)
    ninv = np.zeros((R, n_pad), dtype=np.float32)
    esrc = np.zeros((R, e_pad), dtype=np.int32)
    edst = np.zeros((R, e_pad), dtype=np.int32)
    emask = np.zeros((R, e_pad), dtype=np.float32)
    einv = np.zeros((R, e_pad), dtype=np.float32)
    for r, g in enumerate(graphs):
        gid[r, :g.n_nodes] = g.global_ids
        nmask[r, :g.n_nodes] = 1.0
        ninv[r, :g.n_nodes] = g.node_inv_mult
        esrc[r, :g.n_edges] = g.edges[:, 0]
        edst[r, :g.n_edges] = g.edges[:, 1]
        emask[r, :g.n_edges] = 1.0
        einv[r, :g.n_edges] = g.edge_inv_mult
    return PartitionedGraphs(
        R=R, n_global=n_global,
        global_ids=gid, node_mask=nmask, node_inv_mult=ninv,
        edge_src=esrc, edge_dst=edst, edge_mask=emask, edge_inv_mult=einv,
        halo=build_halo_plan(graphs, pad_to=pad_to),
    )


def build_2d_halo_rounds(graphs: List[RankGraph], grid: Tuple[int, int],
                         axes: Tuple[str, str] = ("data", "model"),
                         pad_to: int = 8):
    """Two-level halo plan: sub-graphs laid out on a (Ga, Gb) grid spanning
    TWO mesh axes; every neighbor shift (da, db) becomes one exchange round
    routed as <=2 chained ppermute hops (uniform torus translation — no
    relay conflicts). Rank id = a * Gb + b, a over axes[0], b over axes[1].

    Returns (rounds2d, nbr arrays [R, K, B]) to splice into a HaloPlan /
    ``ShardedGraph.with_arrays``.
    """
    Ga, Gb = grid
    R = len(graphs)
    assert R == Ga * Gb
    g2l = [{int(g): i for i, g in enumerate(gr.global_ids)} for gr in graphs]

    shifts = [(da, db) for da in (-1, 0, 1) for db in (-1, 0, 1)
              if not (da == 0 and db == 0)]
    # shared-id lists per (rank, shift)
    shared: Dict[Tuple[int, int], np.ndarray] = {}
    maxb = 1
    for r in range(R):
        a, b = divmod(r, Gb)
        for si, (da, db) in enumerate(shifts):
            a2, b2 = a + da, b + db
            if not (0 <= a2 < Ga and 0 <= b2 < Gb):
                continue
            s = a2 * Gb + b2
            common = np.intersect1d(graphs[r].global_ids, graphs[s].global_ids,
                                    assume_unique=True)
            if common.size:
                shared[(r, si)] = common
                maxb = max(maxb, common.size)

    B = _round_up(maxb, pad_to)
    K = len(shifts)
    send_idx = np.zeros((R, K, B), dtype=np.int32)
    send_mask = np.zeros((R, K, B), dtype=np.float32)
    recv_idx = np.zeros((R, K, B), dtype=np.int32)
    recv_mask = np.zeros((R, K, B), dtype=np.float32)
    rounds2d = []
    for si, (da, db) in enumerate(shifts):
        # ppermute perms are indexed ALONG the named axis (the shift applies
        # uniformly across the other axis)
        hops = []
        if db:
            hops.append((axes[1], tuple((b, b + db) for b in range(Gb)
                                        if 0 <= b + db < Gb)))
        if da:
            hops.append((axes[0], tuple((a, a + da) for a in range(Ga)
                                        if 0 <= a + da < Ga)))
        rounds2d.append(tuple(hops))
        for r in range(R):
            common = shared.get((r, si))
            if common is None:
                continue
            a, b = divmod(r, Gb)
            s = (a + da) * Gb + (b + db)
            n = common.size
            send_idx[r, si, :n] = [g2l[r][int(g)] for g in common]
            send_mask[r, si, :n] = 1.0
            recv_idx[s, si, :n] = [g2l[s][int(g)] for g in common]
            recv_mask[s, si, :n] = 1.0
    arrays = dict(nbr_send_idx=send_idx, nbr_send_mask=send_mask,
                  nbr_recv_idx=recv_idx, nbr_recv_mask=recv_mask)
    return tuple(rounds2d), arrays


# ---------------------------------------------------------------------------
# convenience front doors
# ---------------------------------------------------------------------------

def partition_mesh(mesh: SEMMesh, rank_grid: Sequence[int], pad_to: int = 8,
                   method: str = "block") -> PartitionedGraphs:
    """Partition an SEM mesh onto ``prod(rank_grid)`` ranks.

    ``method="block"`` is the NekRS-style element-block decomposition along
    the rank grid (d_ij > 1 coincident GLL copies); ``method="spectral"``
    runs recursive spectral bisection + KL refinement on the mesh graph
    (``repro.core.partition_quality``) and builds a vertex-cut edge
    partition (d_ij == 1).  Consistency (Eqs. 2, 3) holds either way — the
    choice only moves halo volume and balance.
    """
    R = int(np.prod(rank_grid))
    if method == "block":
        e2r = partition_elements(mesh, rank_grid)
        return pack(from_element_partition(mesh, e2r, R), mesh.n_nodes,
                    pad_to=pad_to)
    if method == "spectral":
        from repro.core.mesh_gen import mesh_graph_edges
        from repro.core.partition_quality import mesh_node2part
        node2part = mesh_node2part(mesh, R)
        directed = undirected_to_directed(mesh_graph_edges(mesh))
        return pack(from_edge_partition(mesh.n_nodes, directed, R,
                                        node2part=node2part),
                    mesh.n_nodes, pad_to=pad_to)
    raise ValueError(f"unknown partition method {method!r} "
                     "(expected 'block' or 'spectral')")


def partition_graph(n_nodes: int, directed_edges: np.ndarray, R: int,
                    pad_to: int = 8, assign: str = "dst",
                    method: str = "block",
                    node2part: np.ndarray = None) -> PartitionedGraphs:
    """Partition an arbitrary directed graph onto R ranks.

    ``node2part`` (any [N] int array, ranks may even be empty) wins over
    ``method``; otherwise ``method="block"`` keeps the contiguous index
    split and ``method="spectral"`` computes a node2part with
    :func:`repro.core.partition_quality.spectral_node2part`.
    """
    if node2part is None and method == "spectral":
        from repro.core.partition_quality import spectral_node2part
        node2part = spectral_node2part(n_nodes, directed_edges, R)
    elif node2part is None and method != "block":
        raise ValueError(f"unknown partition method {method!r} "
                         "(expected 'block' or 'spectral')")
    return pack(from_edge_partition(n_nodes, directed_edges, R,
                                    node2part=node2part, assign=assign),
                n_nodes, pad_to=pad_to)


def gather_node_features(pg: PartitionedGraphs, global_x: np.ndarray) -> np.ndarray:
    """[n_global, F] -> [R, N_pad, F]; coincident copies get identical rows."""
    safe = np.clip(pg.global_ids, 0, None)
    out = global_x[safe.reshape(-1)].reshape(pg.R, pg.n_pad, -1)
    return out * pg.node_mask[..., None]


def scatter_node_outputs(pg: PartitionedGraphs, per_rank_y: np.ndarray) -> np.ndarray:
    """Inverse of gather (Eq. 2's "cat" by global index): [R, N_pad, F] -> [n_global, F].

    Coincident copies are asserted consistent by taking any owner's row.
    """
    F = per_rank_y.shape[-1]
    out = np.zeros((pg.n_global, F), dtype=per_rank_y.dtype)
    for r in range(pg.R):
        m = pg.node_mask[r] > 0
        out[pg.global_ids[r, m]] = per_rank_y[r, m]
    return out
