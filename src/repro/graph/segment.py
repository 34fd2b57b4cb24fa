"""Segment-op wrappers: the message-passing scatter/gather primitives.

JAX has no native sparse message passing (BCOO only) — per the assignment,
message passing IS implemented via ``jax.ops.segment_sum``-family ops over an
edge index. These wrappers fix num_segments statically and add masked and
softmax variants used across the GNN zoo.  On a static graph of bounded
degree, ``slot_segment_sum`` / ``slot_gather`` do the same sum and gather
through per-node slot tables, so that neither they nor their VJPs scatter.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30
LANES = 128
#: widest rows that ``slot_segment_sum`` gathers as they are; wider rows
#: that divide 128 lanes are gathered 128 lanes at a time.  XLA keeps
#: narrow edge rows feature-major on the TPU, so one row gather reads F
#: strided words: on a TPU v5e, summing 6 slots over 274,632 nodes took
#: 7.8 ms at F=8 and 60.7 ms at F=32 that way, and 10.9 ms and 16.0 ms with
#: 128-lane rows of 128/F edges gathered (a scatter-add: 25 ms at either).
NARROW = 8


def segment_sum(data: jnp.ndarray, segment_ids: jnp.ndarray, num_segments: int) -> jnp.ndarray:
    return jax.ops.segment_sum(data, segment_ids, num_segments=num_segments)


def segment_max(data: jnp.ndarray, segment_ids: jnp.ndarray, num_segments: int) -> jnp.ndarray:
    return jax.ops.segment_max(data, segment_ids, num_segments=num_segments)


def segment_mean(data: jnp.ndarray, segment_ids: jnp.ndarray, num_segments: int,
                 eps: float = 1e-9) -> jnp.ndarray:
    s = segment_sum(data, segment_ids, num_segments)
    c = segment_sum(jnp.ones(data.shape[:1], data.dtype), segment_ids, num_segments)
    return s / jnp.maximum(c, eps)[(...,) + (None,) * (data.ndim - 1)]


def segment_softmax(logits: jnp.ndarray, segment_ids: jnp.ndarray, num_segments: int,
                    mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """Numerically-stable softmax over segments (e.g. GAT edge softmax).

    ``logits``: [E, ...]; mask: [E] 1/0 — masked entries get weight 0.
    """
    if mask is not None:
        logits = jnp.where(mask[(...,) + (None,) * (logits.ndim - 1)] > 0, logits, NEG_INF)
    seg_max = segment_max(logits, segment_ids, num_segments)
    seg_max = jnp.where(jnp.isfinite(seg_max), seg_max, 0.0)
    shifted = logits - seg_max[segment_ids]
    expv = jnp.exp(shifted)
    if mask is not None:
        expv = expv * mask[(...,) + (None,) * (logits.ndim - 1)]
    denom = segment_sum(expv, segment_ids, num_segments)
    return expv / jnp.maximum(denom[segment_ids], 1e-20)


def gather(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Row gather along the node axis (works with leading batch dims on x)."""
    return jnp.take(x, idx, axis=-2)


# ---------------------------------------------------------------------------
# gather-only segment sum over a static, bounded-degree graph
# ---------------------------------------------------------------------------
#
# ``slots`` is an int32 [N, D] table: row n lists the ids of the edges whose
# ``ids`` entry is n, padded with an out-of-range id (reads zero).  Edges not
# listed in the table (masked edges) contribute nothing to the forward sum;
# their gradient through ``slot_segment_sum`` is ``g[ids]`` and through
# ``slot_gather`` is dropped, so callers zero such edges' data (the NMP layer
# multiplies by ``edge_mask``) to keep each VJP the exact transpose.

@jax.custom_vjp
def slot_segment_sum(data: jnp.ndarray, ids: jnp.ndarray,
                     slots: jnp.ndarray) -> jnp.ndarray:
    """``segment_sum(data, ids, N)`` as a gather: ``out[n] = sum_k
    data[slots[n, k]]``.  ``data`` is ``[..., E, F]``; the result is
    ``[..., N, F]``.  Its VJP is the gather ``g[ids]``."""
    with jax.named_scope("slot_sum"):
        e, f = data.shape[-2:]
        n_slots = slots.shape[-1]
        if f <= NARROW or LANES % f:
            # one [N]-row gather per slot, of the rows as they are
            acc = jnp.take(data, slots[:, 0], axis=-2, mode="fill", fill_value=0)
            for k in range(1, n_slots):
                acc = acc + jnp.take(data, slots[:, k], axis=-2, mode="fill",
                                     fill_value=0)
            return acc
        # one [N]-row gather per slot of whole 128-lane rows of ``pack``
        # edges; each keeps its edge's f lanes, and the lane groups are
        # folded once at the end
        pack = LANES // f
        lead = data.shape[:-2]
        # zero rows from id E on: a padding slot (id E) reads zeros, and the
        # gather clamps its indices instead of masking what it read
        data = jnp.pad(data, [(0, 0)] * len(lead) + [(0, pack - e % pack), (0, 0)])
        rows = data.reshape(lead + (-1, LANES))
        lane_group = jnp.arange(LANES) // f
        acc = 0
        for k in range(n_slots):
            s = slots[:, k]
            got = jnp.take(rows, s // pack, axis=-2, mode="clip")
            acc = acc + jnp.where((s % pack)[:, None] == lane_group, got, 0)
        return acc.reshape(acc.shape[:-1] + (pack, f)).sum(axis=-2)


def _slot_segment_sum_fwd(data, ids, slots):
    return slot_segment_sum(data, ids, slots), (ids, slots)


def _slot_segment_sum_bwd(res, g):
    ids, slots = res
    return slot_gather(g, ids, slots), None, None


slot_segment_sum.defvjp(_slot_segment_sum_fwd, _slot_segment_sum_bwd)


@jax.custom_vjp
def slot_gather(x: jnp.ndarray, idx: jnp.ndarray,
                slots: jnp.ndarray) -> jnp.ndarray:
    """``x[..., idx, :]`` whose VJP is ``slot_segment_sum(g, idx, slots)``
    (a gather) instead of a scatter-add."""
    return gather(x, idx)


def _slot_gather_fwd(x, idx, slots):
    return gather(x, idx), (idx, slots)


def _slot_gather_bwd(res, g):
    idx, slots = res
    return slot_segment_sum(g, idx, slots), None, None


slot_gather.defvjp(_slot_gather_fwd, _slot_gather_bwd)
