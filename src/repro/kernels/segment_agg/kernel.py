"""Pallas TPU kernels: fused edge-MLP + destination-aligned segment-sum.

The paper's NMP hot loop is (edge MLP -> 1/d_ij-weighted aggregate). A naive
XLA lowering writes the MLP output to HBM, re-reads it for the scatter-add,
and the scatter itself is serialized.

Two generations of kernels live here:

* ``edge_mlp_agg`` — the original forward-only op over pre-gathered
  ``[E, 3H]`` features (microbenchmark / oracle target). It consumes the
  legacy dst-aligned block layout (``ops.dst_aligned_layout``) and
  aggregates through a *block-local* ``[BE, block_n]`` one-hot matmul — an
  MXU op whose cost is O(E · block_n · H), i.e. linear in E for a fixed
  block size (block_n is a tile constant, never the node count).

* ``nmp_edge_mlp_agg_fwd`` / ``nmp_edge_mlp_agg_bwd`` — the production pair
  behind the fused NMP registry cells (``NMPPlan(backend="fused")``),
  rewritten around
  **scalar-prefetch DMA gathers**: per-tile src/dst node-id lists are
  prefetched into SMEM (``pltpu.PrefetchScalarGridSpec``) and drive
  dynamic-slice row copies of node features out of HBM/ANY memory into a
  double-buffered VMEM scratch (tile t+1's rows stream in while tile t
  computes). The earlier generation gathered rows via ``[BE, N_round]``
  one-hot MXU matmuls, making the per-tile cost O(E·N·H) and forcing the
  whole node array to live in VMEM; the DMA gathers cost O(E·H) bytes and
  O(1) VMEM rows per edge, so the fused layer's arithmetic scales with the
  *edge* count — the regime the paper's Frontier runs assume. No one-hot
  gather/scatter matrices are materialized anywhere in the fused pair: the
  aggregation and the backward's node-gradient both run as per-row
  read-modify-write updates against a VMEM accumulator.

Mixed precision: ``precision="bf16"`` runs every edge-MLP matmul with
bf16 operands accumulating into fp32 (``preferred_element_type``); the
aggregation accumulator and all gradient accumulators stay fp32 either way.
``precision="fp32"`` (default) is bit-stable with the XLA reference modulo
summation order and is what the consistency tests pin.

VMEM note: the fused forward holds the ``[N_round, H]`` *aggregate* (and
the backward the node-gradient accumulator) in VMEM; the node features
stay in HBM/ANY and are streamed by lane-padded rows (:func:`lane_pad`).
SMEM note: the prefetched index lists are ``[n_tiles, BE]`` int32, 4 bytes
per edge slot each. :func:`check_fits` refuses a graph past either limit
before tracing (on a v5e, about 21k nodes per rank at H=32); shard the
graph over more ranks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

FP32 = "fp32"
BF16 = "bf16"
PRECISIONS = (FP32, BF16)


def _dot(a, b, precision: str):
    """Matmul with the kernel's precision policy: bf16 operands / fp32
    accumulation when ``precision == "bf16"``, true fp32 otherwise (as in
    ``repro.nn.dense``)."""
    if precision == BF16:
        return jax.lax.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32)
    return jax.lax.dot(a, b, precision=jax.lax.Precision.HIGHEST)


def _kernel(feats_ref, dstl_ref, wgt_ref, w1_ref, b1_ref, w2_ref, b2_ref,
            enew_ref, agg_ref, acc_scr, *, block_n: int, block_e: int):
    ej = pl.program_id(1)
    ne = pl.num_programs(1)

    @pl.when(ej == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    feats = feats_ref[0, 0].astype(jnp.float32)          # [BE, Fin]
    h = jax.lax.dot(feats, w1_ref[...].astype(jnp.float32)) + b1_ref[...]
    h = jax.nn.elu(h)
    e_new = jax.lax.dot(h, w2_ref[...].astype(jnp.float32)) + b2_ref[...]
    enew_ref[0, 0] = e_new.astype(enew_ref.dtype)

    # dst-local one-hot [BE, BN]: aggregation as an MXU matmul, not a scatter
    # (BN = block_n, a tile constant — this is O(E·BN·H), linear in E)
    dstl = dstl_ref[0, 0]                                # [BE] in [0, BN)
    wgt = wgt_ref[0, 0]                                  # [BE] (0 on padding)
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (block_e, block_n), 1)
              == dstl[:, None]).astype(jnp.float32) * wgt[:, None]
    acc_scr[...] += jax.lax.dot_general(
        onehot, e_new, (((0,), (0,)), ((), ())))         # [BN, H]

    @pl.when(ej == ne - 1)
    def _flush():
        agg_ref[0] = acc_scr[...].astype(agg_ref.dtype)


# ---------------------------------------------------------------------------
# scalar-prefetch DMA gather / scatter helpers (shared by the fused pair)
# ---------------------------------------------------------------------------

#: lanes of one TPU vector tile: a row DMA moves whole lane tiles
LANES = 128
#: scoped VMEM the fused kernels may use (XLA's default is 16 MiB; a v5e
#: core has 128 MiB)
VMEM_LIMIT_BYTES = 64 << 20
COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)
#: SMEM for scalar-prefetched index lists: a v5e core has 1 MiB, of which
#: Mosaic keeps about 1 KiB for itself (4 KiB are held back)
SMEM_BUDGET_BYTES = (1 << 20) - (4 << 10)


def _row_bytes(width: int) -> int:
    """VMEM bytes of one fp32 row: the minor dim is padded to whole lanes."""
    return -(-width // LANES) * LANES * 4


def check_fits(kernel: str, smem_bytes: int, vmem_bytes: int) -> None:
    """Refuse, before tracing, a call whose index lists exceed SMEM or whose
    resident arrays exceed the scoped VMEM limit: the design keeps every
    index list in SMEM and each full-size accumulator in VMEM (ROADMAP R1
    lifts both). Only compiled (``interpret=False``) calls are checked."""
    if smem_bytes > SMEM_BUDGET_BYTES:
        raise ValueError(
            f"{kernel}: the scalar-prefetched index lists need {smem_bytes} B "
            f"of SMEM, over the {SMEM_BUDGET_BYTES} B budget of a TPU core's "
            "1 MiB SMEM; partition the graph over more ranks")
    if vmem_bytes > VMEM_LIMIT_BYTES:
        raise ValueError(
            f"{kernel}: the resident accumulators need about {vmem_bytes} B "
            f"of VMEM, over the kernel's {VMEM_LIMIT_BYTES} B scoped VMEM "
            "limit; partition the graph over more ranks")


def index_list_bytes(n_tiles: int, block: int) -> int:
    """SMEM bytes of one ``[T, BLOCK]`` int32 index list (SMEM pads the
    rows to a multiple of 8 and the columns to whole lanes)."""
    return -(-n_tiles // 8) * 8 * _row_bytes(block)


def check_fused_nmp_fits(n_round: int, hidden: int, n_tiles: int,
                         block_e: int, *, backward: bool) -> None:
    """Size limit of the fused NMP pair: two ``[T, BE]`` int32 index lists in
    SMEM; the ``[N_round, H]`` accumulator twice in VMEM (scratch and output
    block) beside the edge tiles (about 16 ``[BE, 128]`` fp32 buffers in the
    forward, 24 in the backward)."""
    tiles = (24 if backward else 16) * block_e * _row_bytes(LANES)
    check_fits("nmp_edge_mlp_agg_bwd" if backward else "nmp_edge_mlp_agg_fwd",
               2 * index_list_bytes(n_tiles, block_e),
               2 * n_round * _row_bytes(hidden) + tiles)


def lane_pad(x):
    """Zero-pad the minor dim of ``x`` [N, F] up to a multiple of 128 lanes.

    Mosaic refuses a row DMA narrower than one lane tile ("Slice shape along
    dimension 1 must be aligned to tiling (128), but is 32"), so the HBM
    operands of the row gathers carry lane-padded rows and the kernels slice
    the real width back out of the gathered tile.
    """
    f = x.shape[-1]
    f_pad = -(-f // LANES) * LANES
    return x if f_pad == f else jnp.pad(x, ((0, 0), (0, f_pad - f)))


def _gather_rows(idx_ref, t, nt, src_ref, buf, sem, block_e: int):
    """Double-buffered row gather: rows ``idx_ref[t, :]`` of ``src_ref``
    (HBM/ANY, lane-padded by :func:`lane_pad`) land in ``buf[t % 2]`` (VMEM
    ``[2, BE, F_pad]``).

    At tile t the copies for tile t+1 are issued into the other slot before
    waiting on tile t's — the next tile's rows stream in under this tile's
    compute. The SMEM-resident index list (scalar prefetch) is what makes
    reading tile t+1's indices ahead of the grid possible.
    """
    def issue(tt, slot):
        def body(k, _):
            pltpu.make_async_copy(
                src_ref.at[pl.ds(idx_ref[tt, k], 1)],
                buf.at[slot, pl.ds(k, 1)], sem.at[slot]).start()
            return 0
        jax.lax.fori_loop(0, block_e, body, 0)

    @pl.when(t == 0)
    def _first():
        issue(0, 0)

    @pl.when(t + 1 < nt)
    def _ahead():
        issue(t + 1, (t + 1) % 2)

    def wait(k, _):
        pltpu.make_async_copy(
            src_ref.at[pl.ds(idx_ref[t, k], 1)],
            buf.at[t % 2, pl.ds(k, 1)], sem.at[t % 2]).wait()
        return 0
    jax.lax.fori_loop(0, block_e, wait, 0)
    return buf[t % 2]


def _scatter_add_rows(idx_ref, t, rows, rows_scr, acc, block_e: int):
    """Sequential per-row read-modify-write: ``acc[idx_ref[t, k]] += rows[k]``.

    ``rows`` is staged in the VMEM scratch ``rows_scr`` first: a dynamic row
    slice of a ref lowers on the TPU, a dynamic slice of a value does not.
    Duplicate destinations within the tile are handled by the loop's
    sequential semantics; padding slots carry zero rows (weight-masked), so
    their writes to row 0 are no-ops.
    """
    rows_scr[...] = rows.astype(rows_scr.dtype)

    def body(k, _):
        r = idx_ref[t, k]
        acc[pl.ds(r, 1), :] = acc[pl.ds(r, 1), :] + rows_scr[pl.ds(k, 1), :]
        return 0
    jax.lax.fori_loop(0, block_e, body, 0)


def _elu(h):
    """ELU from ``exp``: Pallas has no TPU lowering for ``expm1``, which
    ``jax.nn.elu`` uses (the two agree to fp32 rounding)."""
    return jnp.where(h > 0, h, jnp.exp(jnp.minimum(h, 0.0)) - 1.0)


def _edge_mlp_tile(xi, xj, et, mask, w0, b0, wrest, brest, lng, lnb, *,
                   hidden: int, n_hidden: int, has_ln: bool, precision: str,
                   eps: float = 1e-5):
    """Eq. 4a on one ``[BE, H]`` tile: the first dense layer runs as three
    H-slices of w0 over the *virtual* concat [xi ++ xj ++ e] (the ``[BE, 3H]``
    tensor is never materialized), then the hidden stack, LayerNorm, residual
    and edge mask. Matmuls follow the ``precision`` policy; every other op
    (ELU, LN statistics, residual) stays fp32."""
    h = (_dot(xi, w0[:hidden], precision)
         + _dot(xj, w0[hidden:2 * hidden], precision)
         + _dot(et, w0[2 * hidden:], precision) + b0[0])
    for l in range(n_hidden):
        h = _elu(h)
        h = _dot(h, wrest[l], precision) + brest[l]
    if has_ln:
        mu = jnp.mean(h, axis=-1, keepdims=True)
        var = jnp.var(h, axis=-1, keepdims=True)
        h = (h - mu) * jax.lax.rsqrt(var + eps)
        h = h * lng[0] + lnb[0]
    return (et + h) * mask


# ---------------------------------------------------------------------------
# fused NMP forward
# ---------------------------------------------------------------------------

def _nmp_fwd_kernel(srcg_ref, dstg_ref, x_any, e_ref, emask_ref, einv_ref,
                    w0_ref, b0_ref, wrest_ref, brest_ref, lng_ref, lnb_ref,
                    enew_ref, agg_ref, xi_buf, xj_buf, rows_scr, agg_scr,
                    sem_src, sem_dst, *, block_e: int, hidden: int,
                    n_hidden: int, has_ln: bool, precision: str):
    """Fused Eq. 4a+4b tile: DMA-gather src/dst node rows, run the full
    residual edge MLP (incl. LayerNorm), mask, and scatter the 1/d_ij-
    weighted contribution into the fp32 VMEM aggregate."""
    t = pl.program_id(0)
    nt = pl.num_programs(0)

    @pl.when(t == 0)
    def _init():
        agg_scr[...] = jnp.zeros_like(agg_scr)

    xi = _gather_rows(srcg_ref, t, nt, x_any, xi_buf, sem_src,
                      block_e)[:, :hidden].astype(jnp.float32)   # [BE, H]
    xj = _gather_rows(dstg_ref, t, nt, x_any, xj_buf, sem_dst,
                      block_e)[:, :hidden].astype(jnp.float32)   # [BE, H]
    et = e_ref[0].astype(jnp.float32)                     # [BE, H]
    mask = emask_ref[0].T                                 # [BE, 1] 1/0
    wgt = einv_ref[0].T                                   # [BE, 1] 1/d_ij (0 pad)

    e_new = _edge_mlp_tile(
        xi, xj, et, mask, w0_ref[...].astype(jnp.float32),
        b0_ref[...].astype(jnp.float32), wrest_ref[...].astype(jnp.float32),
        brest_ref[...].astype(jnp.float32), lng_ref[...].astype(jnp.float32),
        lnb_ref[...].astype(jnp.float32), hidden=hidden, n_hidden=n_hidden,
        has_ln=has_ln, precision=precision)
    enew_ref[0] = e_new.astype(enew_ref.dtype)

    _scatter_add_rows(dstg_ref, t, e_new * wgt, rows_scr, agg_scr, block_e)

    @pl.when(t == nt - 1)
    def _flush():
        agg_ref[...] = agg_scr[...].astype(agg_ref.dtype)


def nmp_edge_mlp_agg_fwd(x, e_tiles, srcg, dstg, emask, einv, w0, b0, wrest,
                         brest, lng, lnb, *, block_e: int, n_hidden: int,
                         has_ln: bool, precision: str = FP32,
                         interpret: bool = False):
    """Fused NMP forward. ``x``: [N_round, H] node features (HBM-resident;
    only gathered rows enter VMEM); ``e_tiles``: [T, BE, H] dst-sorted edge
    tiles; ``srcg``/``dstg``: [T, BE] global src/dst node ids per slot
    (scalar-prefetched to SMEM, 0 on padding); ``emask``/``einv``: [T, BE]
    edge mask and 1/d_ij (both 0 on padding slots).

    Returns (e_new [T, BE, H], agg [N_round, H] fp32).
    """
    T, BE, H = e_tiles.shape
    Lp = wrest.shape[0]
    n_round = x.shape[0]
    x = lane_pad(x)
    if not interpret:
        check_fused_nmp_fits(n_round, H, T, BE, backward=False)
    kern = functools.partial(
        _nmp_fwd_kernel, block_e=BE, hidden=H, n_hidden=n_hidden,
        has_ln=has_ln, precision=precision)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),              # x (row DMA)
            pl.BlockSpec((1, BE, H), lambda t, *_: (t, 0, 0)),
            pl.BlockSpec((1, 1, BE), lambda t, *_: (t, 0, 0)),
            pl.BlockSpec((1, 1, BE), lambda t, *_: (t, 0, 0)),
            pl.BlockSpec((3 * H, H), lambda t, *_: (0, 0)),
            pl.BlockSpec((1, H), lambda t, *_: (0, 0)),
            pl.BlockSpec((Lp, H, H), lambda t, *_: (0, 0, 0)),
            pl.BlockSpec((Lp, H), lambda t, *_: (0, 0)),
            pl.BlockSpec((1, H), lambda t, *_: (0, 0)),
            pl.BlockSpec((1, H), lambda t, *_: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, BE, H), lambda t, *_: (t, 0, 0)),
            pl.BlockSpec((n_round, H), lambda t, *_: (0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, BE, x.shape[1]), x.dtype),          # xi double-buf
            pltpu.VMEM((2, BE, x.shape[1]), x.dtype),          # xj double-buf
            pltpu.VMEM((BE, H), jnp.float32),                  # scatter rows
            pltpu.VMEM((n_round, H), jnp.float32),             # aggregate
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((T, BE, H), e_tiles.dtype),
            jax.ShapeDtypeStruct((n_round, H), jnp.float32),
        ],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(srcg, dstg, x, e_tiles, emask[:, None], einv[:, None], w0, b0, wrest,
      brest, lng, lnb)


# ---------------------------------------------------------------------------
# fused NMP backward
# ---------------------------------------------------------------------------

def _nmp_bwd_kernel(srcg_ref, dstg_ref, x_any, gagg_any, e_ref, emask_ref,
                    einv_ref, w0_ref, b0_ref, wrest_ref, brest_ref, lng_ref,
                    lnb_ref, genew_ref,
                    gx_ref, ge_ref, gw0_ref, gb0_ref, gwrest_ref, gbrest_ref,
                    glng_ref, glnb_ref,
                    xi_buf, xj_buf, gag_buf, rows_scr, gx_scr, gw0_scr, gb0_scr,
                    gwrest_scr, gbrest_scr, glng_scr, glnb_scr, sem_src,
                    sem_dst, sem_gag, *, block_e: int, hidden: int,
                    n_hidden: int, has_ln: bool, precision: str):
    """Backward of the fused NMP tile: per-tile VJP of the recomputed edge
    MLP over DMA-gathered node rows.

    The aggregate's cotangent enters as gathered rows of ``g_agg`` (the
    adjoint of a row scatter-add is a row gather scaled by the same 1/d_ij
    weight); grads w.r.t. the gathered xi/xj rows are scattered back into a
    full-size VMEM node-grad accumulator by the same per-row RMW loop the
    forward aggregation uses. Weight grads accumulate in VMEM scratch across
    the grid; everything flushes to HBM on the final tile.
    """
    t = pl.program_id(0)
    nt = pl.num_programs(0)

    @pl.when(t == 0)
    def _init():
        gx_scr[...] = jnp.zeros_like(gx_scr)
        gw0_scr[...] = jnp.zeros_like(gw0_scr)
        gb0_scr[...] = jnp.zeros_like(gb0_scr)
        gwrest_scr[...] = jnp.zeros_like(gwrest_scr)
        gbrest_scr[...] = jnp.zeros_like(gbrest_scr)
        glng_scr[...] = jnp.zeros_like(glng_scr)
        glnb_scr[...] = jnp.zeros_like(glnb_scr)

    xi = _gather_rows(srcg_ref, t, nt, x_any, xi_buf, sem_src,
                      block_e)[:, :hidden].astype(jnp.float32)
    xj = _gather_rows(dstg_ref, t, nt, x_any, xj_buf, sem_dst,
                      block_e)[:, :hidden].astype(jnp.float32)
    gag = _gather_rows(dstg_ref, t, nt, gagg_any, gag_buf, sem_gag,
                       block_e)[:, :hidden].astype(jnp.float32)
    mask = emask_ref[0].T
    wgt = einv_ref[0].T

    def tile_fwd(xi, xj, et, w0, b0, wrest, brest, lng, lnb):
        # identical arithmetic to the forward tile (incl. the precision
        # policy, so bf16 truncation is differentiated through)
        return _edge_mlp_tile(xi, xj, et, mask, w0, b0, wrest, brest, lng,
                              lnb, hidden=hidden, n_hidden=n_hidden,
                              has_ln=has_ln, precision=precision)

    args = (xi, xj, e_ref[0].astype(jnp.float32),
            w0_ref[...].astype(jnp.float32),
            b0_ref[...].astype(jnp.float32),
            wrest_ref[...].astype(jnp.float32),
            brest_ref[...].astype(jnp.float32),
            lng_ref[...].astype(jnp.float32),
            lnb_ref[...].astype(jnp.float32))
    _, vjp = jax.vjp(tile_fwd, *args)
    # e_new feeds both outputs: its cotangent is g_enew plus the weighted
    # rows of g_agg its scatter-add contributed to
    g_e_new = genew_ref[0].astype(jnp.float32) + gag * wgt
    gxi, gxj, ge, gw0, gb0, gwrest, gbrest, glng, glnb = vjp(g_e_new)

    ge_ref[0] = ge.astype(ge_ref.dtype)
    _scatter_add_rows(srcg_ref, t, gxi, rows_scr, gx_scr, block_e)
    _scatter_add_rows(dstg_ref, t, gxj, rows_scr, gx_scr, block_e)
    gw0_scr[...] += gw0
    gb0_scr[...] += gb0
    gwrest_scr[...] += gwrest
    gbrest_scr[...] += gbrest
    glng_scr[...] += glng
    glnb_scr[...] += glnb

    @pl.when(t == nt - 1)
    def _flush():
        gx_ref[...] = gx_scr[...].astype(gx_ref.dtype)
        gw0_ref[...] = gw0_scr[...].astype(gw0_ref.dtype)
        gb0_ref[...] = gb0_scr[...].astype(gb0_ref.dtype)
        gwrest_ref[...] = gwrest_scr[...].astype(gwrest_ref.dtype)
        gbrest_ref[...] = gbrest_scr[...].astype(gbrest_ref.dtype)
        glng_ref[...] = glng_scr[...].astype(glng_ref.dtype)
        glnb_ref[...] = glnb_scr[...].astype(glnb_ref.dtype)


def nmp_edge_mlp_agg_bwd(x, e_tiles, srcg, dstg, emask, einv, w0, b0, wrest,
                         brest, lng, lnb, g_enew, g_agg, *, block_e: int,
                         n_hidden: int, has_ln: bool, precision: str = FP32,
                         interpret: bool = False):
    """Backward Pallas kernel for the fused NMP op.

    ``g_agg`` stays HBM/ANY-resident like ``x``; its rows are DMA-gathered
    per tile. Returns (g_x [N_round, H], g_e [T, BE, H], g_w0, g_b0,
    g_wrest, g_brest, g_lng, g_lnb), all fp32.
    """
    T, BE, H = e_tiles.shape
    Lp = wrest.shape[0]
    n_round = x.shape[0]
    x, g_agg = lane_pad(x), lane_pad(g_agg)
    if not interpret:
        check_fused_nmp_fits(n_round, H, T, BE, backward=True)
    kern = functools.partial(
        _nmp_bwd_kernel, block_e=BE, hidden=H, n_hidden=n_hidden,
        has_ln=has_ln, precision=precision)
    f32 = jnp.float32
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),              # x
            pl.BlockSpec(memory_space=pl.ANY),              # g_agg
            pl.BlockSpec((1, BE, H), lambda t, *_: (t, 0, 0)),
            pl.BlockSpec((1, 1, BE), lambda t, *_: (t, 0, 0)),
            pl.BlockSpec((1, 1, BE), lambda t, *_: (t, 0, 0)),
            pl.BlockSpec((3 * H, H), lambda t, *_: (0, 0)),
            pl.BlockSpec((1, H), lambda t, *_: (0, 0)),
            pl.BlockSpec((Lp, H, H), lambda t, *_: (0, 0, 0)),
            pl.BlockSpec((Lp, H), lambda t, *_: (0, 0)),
            pl.BlockSpec((1, H), lambda t, *_: (0, 0)),
            pl.BlockSpec((1, H), lambda t, *_: (0, 0)),
            pl.BlockSpec((1, BE, H), lambda t, *_: (t, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((n_round, H), lambda t, *_: (0, 0)),
            pl.BlockSpec((1, BE, H), lambda t, *_: (t, 0, 0)),
            pl.BlockSpec((3 * H, H), lambda t, *_: (0, 0)),
            pl.BlockSpec((1, H), lambda t, *_: (0, 0)),
            pl.BlockSpec((Lp, H, H), lambda t, *_: (0, 0, 0)),
            pl.BlockSpec((Lp, H), lambda t, *_: (0, 0)),
            pl.BlockSpec((1, H), lambda t, *_: (0, 0)),
            pl.BlockSpec((1, H), lambda t, *_: (0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, BE, x.shape[1]), x.dtype),          # xi double-buf
            pltpu.VMEM((2, BE, x.shape[1]), x.dtype),          # xj double-buf
            pltpu.VMEM((2, BE, x.shape[1]), g_agg.dtype),      # g_agg rows
            pltpu.VMEM((BE, H), f32),                          # scatter rows
            pltpu.VMEM((n_round, H), f32),                     # g_x accum
            pltpu.VMEM((3 * H, H), f32),
            pltpu.VMEM((1, H), f32),
            pltpu.VMEM((Lp, H, H), f32),
            pltpu.VMEM((Lp, H), f32),
            pltpu.VMEM((1, H), f32),
            pltpu.VMEM((1, H), f32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_round, H), f32),
            jax.ShapeDtypeStruct((T, BE, H), f32),
            jax.ShapeDtypeStruct((3 * H, H), f32),
            jax.ShapeDtypeStruct((1, H), f32),
            jax.ShapeDtypeStruct((Lp, H, H), f32),
            jax.ShapeDtypeStruct((Lp, H), f32),
            jax.ShapeDtypeStruct((1, H), f32),
            jax.ShapeDtypeStruct((1, H), f32),
        ],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(srcg, dstg, x, g_agg, e_tiles, emask[:, None], einv[:, None], w0, b0,
      wrest, brest, lng, lnb, g_enew)


def edge_mlp_agg(feats, dst_local, weights, w1, b1, w2, b2, *,
                 n_node_blocks: int, block_n: int, block_e: int,
                 interpret: bool = False):
    """feats: [NB, NE, BE, Fin] dst-aligned tiles (see ops.dst_aligned_layout);
    dst_local: [NB, NE, BE] in [0, BN); weights: same shape (0 = padding).

    Returns (e_new [NB, NE, BE, H], agg [NB, BN, H]).
    """
    NB, NE, BE, Fin = feats.shape
    H = w2.shape[1]
    kern = functools.partial(_kernel, block_n=block_n, block_e=block_e)
    return pl.pallas_call(
        kern,
        grid=(NB, NE),
        in_specs=[
            pl.BlockSpec((1, 1, BE, Fin), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, BE), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, BE), lambda i, j: (i, j, 0)),
            pl.BlockSpec((Fin, w1.shape[1]), lambda i, j: (0, 0)),
            pl.BlockSpec((w1.shape[1],), lambda i, j: (0,)),
            pl.BlockSpec((w1.shape[1], H), lambda i, j: (0, 0)),
            pl.BlockSpec((H,), lambda i, j: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, BE, H), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, block_n, H), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((NB, NE, BE, H), feats.dtype),
            jax.ShapeDtypeStruct((NB, block_n, H), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_n, H), jnp.float32)],
        interpret=interpret,
    )(feats, dst_local, weights, w1, b1, w2, b2)
