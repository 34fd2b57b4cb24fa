"""Pallas TPU kernels for the packed halo wire format.

Two tiny data-movement kernels that replace the ``take(send_idx)`` /
``scatter-add`` XLA pattern on the neighbor-exchange hot path:

* ``pack``   — gather boundary rows ``x[idx]`` into a contiguous send
  buffer, multiplied by the 0/1 send mask.  Row gathers are issued as
  double-buffered per-row HBM->VMEM DMAs driven by a scalar-prefetched
  index list, the same machinery as ``kernels/segment_agg``.
* ``unpack`` — masked scatter-add of a recv buffer into the destination
  array: ``out = a.at[idx].add(buf * mask)``.  The accumulator lives in
  a VMEM scratch initialised from ``a`` on the first tile and flushed on
  the last, with sequential per-row read-modify-write (duplicate indices
  within a round cannot race).

Both kernels are pure data movement: the packed halo path must stay
BITWISE equal to the dense path, so there is no re-association of sums —
each output row receives exactly the rows the dense path would add, in
the same tile order.

Index lists ride in SMEM as 2-D ``[T, BLOCK]`` int32 via
``pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=1)``; padding rows
carry index 0 and mask 0.0 so they gather/scatter harmless zeros.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.segment_agg.kernel import (
    COMPILER_PARAMS, _gather_rows, _row_bytes, _scatter_add_rows, check_fits,
    index_list_bytes, lane_pad)


def _pack_kernel(idx_ref, x_any, mask_ref, buf_ref, gat, sem, *, block_b,
                 feat):
    t = pl.program_id(0)
    nt = pl.num_programs(0)
    rows = _gather_rows(idx_ref, t, nt, x_any, gat, sem, block_b)[:, :feat]
    buf_ref[0] = (rows * mask_ref[0].T).astype(buf_ref.dtype)


def pack_pallas(x: jnp.ndarray, idx_t: jnp.ndarray, mask_t: jnp.ndarray,
                *, interpret: bool = False) -> jnp.ndarray:
    """Masked row gather ``x[idx] * mask`` -> tiled ``[T, BB, F]`` buffer.

    ``idx_t``/``mask_t`` are pre-tiled ``[T, BB]`` (int32 / x.dtype);
    padding slots have index 0 and mask 0.
    """
    n_tiles, block_b = idx_t.shape
    feat = x.shape[1]
    if not interpret:
        check_fits("pack_pallas", index_list_bytes(n_tiles, block_b),
                   6 * block_b * _row_bytes(feat))
    x = lane_pad(x)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),          # x: manual DMA
            pl.BlockSpec((1, 1, block_b), lambda t, *_: (t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_b, feat), lambda t, *_: (t, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, block_b, x.shape[1]), x.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_pack_kernel, block_b=block_b, feat=feat),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles, block_b, feat), x.dtype),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(idx_t, x, mask_t[:, None])


def _unpack_kernel(idx_ref, a_ref, buf_ref, mask_ref, out_ref, rows_scr, acc,
                   *, block_b):
    t = pl.program_id(0)
    nt = pl.num_programs(0)

    @pl.when(t == 0)
    def _init():
        acc[...] = a_ref[...]

    rows = buf_ref[0] * mask_ref[0].T
    _scatter_add_rows(idx_ref, t, rows, rows_scr, acc, block_b)

    @pl.when(t == nt - 1)
    def _flush():
        out_ref[...] = acc[...]


def unpack_add_pallas(a: jnp.ndarray, buf_t: jnp.ndarray, idx_t: jnp.ndarray,
                      mask_t: jnp.ndarray, *,
                      interpret: bool = False) -> jnp.ndarray:
    """Masked scatter-add ``a.at[idx].add(buf * mask)`` over tiled inputs.

    ``a`` is ``[N, F]`` with N a multiple of 8; ``buf_t`` is
    ``[T, BB, F]`` in ``a.dtype``; padding slots (index 0, mask 0) add
    exact zeros to row 0.
    """
    n_tiles, block_b = idx_t.shape
    n_rows, feat = a.shape
    if not interpret:
        # the accumulator stays resident twice (scratch and output block)
        check_fits("unpack_add_pallas", index_list_bytes(n_tiles, block_b),
                   (2 * n_rows + 8 * block_b) * _row_bytes(feat))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((n_rows, feat), lambda t, *_: (0, 0)),
            pl.BlockSpec((1, block_b, feat), lambda t, *_: (t, 0, 0)),
            pl.BlockSpec((1, 1, block_b), lambda t, *_: (t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((n_rows, feat), lambda t, *_: (0, 0)),
        scratch_shapes=[pltpu.VMEM((block_b, feat), a.dtype),
                        pltpu.VMEM((n_rows, feat), a.dtype)],
    )
    return pl.pallas_call(
        functools.partial(_unpack_kernel, block_b=block_b),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_rows, feat), a.dtype),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(idx_t, a, buf_t, mask_t[:, None])
