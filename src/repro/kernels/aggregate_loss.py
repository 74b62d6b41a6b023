"""Pallas TPU kernel for Aggregate Risk Analysis (paper Algorithm 3).

TPU adaptation of the paper's GPU kernel.  The GPU version assigns one thread
per trial and reads the ELT direct-access tables from global memory with
per-thread random access, chunking the event axis through shared memory.  A
2,000,000-event catalog of 15 ELTs does not fit the TPU's VMEM, and Mosaic
has no general vector gather, so the two lookup strategies below keep the
table in a form the chip can address:

* ``gather`` (default) — the paper's per-event row read, done by the DMA
  engine.  The ELT stays in HBM, lane-padded to 128 columns so each row is
  one aligned (1, 128) slice.  Grid = (trial_blocks, event_chunks); each
  step reads its (Tb, C) block of event ids from SMEM, issues one row DMA
  per event into a VMEM buffer, waits for all of them and applies the
  occurrence terms.  Each event is looked up exactly once.
* ``onehot`` — gather-free: the ELT is transposed to (M, catalog) and tiled
  along the catalog in lane-aligned tiles; each trial's event ids become a
  (rows_tile, C) one-hot matrix that the MXU multiplies by the tile at
  ``Precision.HIGHEST`` (exact: one 1.0 per column).  Every event is
  compared against every catalog row, so the work grows with the catalog;
  it suits small catalogs only.

Both accumulate the YLT block across event chunks (revisiting output) and
apply the layer aggregate terms on the last visit.  They are validated in
interpret mode against :func:`repro.kernels.ref.aggregate_loss_chunked_ref`
(tests/test_kernels_aggregate.py) and compiled for a described v5e at the
paper's widths (tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import AGG_VARIANTS as VARIANTS, resolve_interpret

LANES = 128
# VMEM budget for one onehot catalog tile: the double-buffered (Mp, rows)
# ELT tile plus the (rows, C) one-hot and row-id planes it is compared with
ONEHOT_VMEM_BUDGET = 8 << 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _aggregate_terms(out_ref, agg_ref):
    """Layer aggregate terms:  min(max(l_T - AggR, 0), AggL)."""
    acc = out_ref[...]
    out_ref[...] = jnp.minimum(jnp.clip(acc - agg_ref[0], 0.0, None),
                               agg_ref[1])


def _gather_kernel(ids_ref, elt_hbm, ret_ref, lim_ref, agg_ref, out_ref,
                   rows, sem, *, n_chunks: int):
    j = pl.program_id(1)
    tb, c = ids_ref.shape

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    def issue(k, carry):
        t, e = k // c, k % c
        pltpu.make_async_copy(elt_hbm.at[pl.ds(ids_ref[t, e], 1)],
                              rows.at[t, pl.ds(e, 1)], sem).start()
        return carry

    def drain(k, carry):               # every copy moves the same bytes
        pltpu.make_async_copy(elt_hbm.at[pl.ds(0, 1)],
                              rows.at[0, pl.ds(0, 1)], sem).wait()
        return carry

    jax.lax.fori_loop(0, tb * c, issue, 0)
    jax.lax.fori_loop(0, tb * c, drain, 0)
    # occurrence terms per ELT:  min(max(l - OccR, 0), OccL); the padded
    # columns carry zero loss and zero terms, so they add nothing
    occ = jnp.clip(rows[...] - ret_ref[...][None], 0.0, None)
    occ = jnp.minimum(occ, lim_ref[...][None])
    out_ref[...] += occ.sum(axis=2).sum(axis=1, keepdims=True)

    @pl.when(j == n_chunks - 1)
    def _agg():
        _aggregate_terms(out_ref, agg_ref)


def _onehot_kernel(ids_ref, elt_ref, ret_ref, lim_ref, agg_ref, out_ref, *,
                   rows_tile: int, n_cat: int, n_chunks: int):
    r = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when((r == 0) & (j == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    tb, c = ids_ref.shape
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (rows_tile, c), 0) \
        + r * rows_tile
    elt = elt_ref[...]                                   # (Mp, rows_tile)
    for t in range(tb):
        # ids outside this tile match no row: a zero column, and with
        # occ_ret >= 0 a zero occurrence loss
        onehot = (row_ids == ids_ref[pl.ds(t, 1), :]).astype(jnp.float32)
        g = jnp.dot(elt, onehot, precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)  # (Mp, C)
        occ = jnp.minimum(jnp.clip(g - ret_ref[...], 0.0, None),
                          lim_ref[...])
        out_ref[pl.ds(t, 1), :] += occ.sum(axis=(0, 1), keepdims=True)

    @pl.when((r == n_cat - 1) & (j == n_chunks - 1))
    def _agg():
        _aggregate_terms(out_ref, agg_ref)


def onehot_rows_tile(num_elts: int, chunk: int) -> int:
    """Catalog rows per onehot tile, from the VMEM footprint as laid out:
    per row, the double-buffered ELT column (``num_elts`` rounded up to 8
    sublanes) plus one one-hot and one row-id entry per event of the chunk
    (``chunk`` rounded up to 128 lanes).  A multiple of 128 (the tile's
    lane axis)."""
    per_row = 4 * (2 * _round_up(num_elts, 8) + 2 * _round_up(chunk, LANES))
    return max(LANES, ONEHOT_VMEM_BUDGET // per_row // LANES * LANES)


def aggregate_loss_pallas(event_ids, elt_losses, occ_ret, occ_lim, agg_ret,
                          agg_lim, *, chunk: int = 128,
                          trial_block: int = 8,
                          rows_tile: Optional[int] = None,
                          interpret: Optional[bool] = None,
                          variant: str = "gather"):
    """Drop-in equivalent of kernels.ref.aggregate_loss_chunked_ref.

    ``variant``: "gather" (per-event row DMA from the HBM-resident table)
    or "onehot" (one-hot x catalog-tile matmul on the MXU; ``rows_tile``
    overrides its tile, rounded up to a multiple of 128).  ``interpret``
    defaults to the platform's choice (:func:`repro.kernels.ops.
    resolve_interpret`)."""
    if variant not in VARIANTS:
        raise ValueError(f"variant {variant!r}: must be one of {VARIANTS}")
    interpret = resolve_interpret(interpret)
    T, K = event_ids.shape
    rows, M = elt_losses.shape
    chunk = min(chunk, K)
    while K % chunk:
        chunk //= 2
    n_chunks = K // chunk
    # the (tb, 1) YLT block needs tb % 8 == 0 (or the whole axis): pad the
    # trial axis with all-pad trials, sliced off below
    tb = min(_round_up(trial_block, 8), _round_up(T, 8))
    T_pad = _round_up(T, tb)
    ids = event_ids.astype(jnp.int32)
    if T_pad != T:
        ids = jnp.pad(ids, ((0, T_pad - T), (0, 0)))
    elt = elt_losses.astype(jnp.float32)
    occ_ret = occ_ret.astype(jnp.float32)
    occ_lim = occ_lim.astype(jnp.float32)
    agg = jnp.stack([jnp.asarray(agg_ret, jnp.float32),
                     jnp.asarray(agg_lim, jnp.float32)])
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out_shape = jax.ShapeDtypeStruct((T_pad, 1), jnp.float32)

    if variant == "gather":
        Mp = _round_up(M, LANES)
        pad_m = lambda a: jnp.pad(a, ((0, 0), (0, Mp - M))) if a.ndim == 2 \
            else jnp.pad(a, (0, Mp - M)).reshape(1, Mp)
        out = pl.pallas_call(
            functools.partial(_gather_kernel, n_chunks=n_chunks),
            grid=(T_pad // tb, n_chunks),
            in_specs=[
                pl.BlockSpec((tb, chunk), lambda i, j: (i, j),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((1, Mp), lambda i, j: (0, 0)),
                pl.BlockSpec((1, Mp), lambda i, j: (0, 0)),
                smem,
            ],
            out_specs=pl.BlockSpec((tb, 1), lambda i, j: (i, 0)),
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((tb, chunk, Mp), jnp.float32),
                            pltpu.SemaphoreType.DMA],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(ids, pad_m(elt), pad_m(occ_ret), pad_m(occ_lim), agg)
        return out[:T, 0]

    Mp = _round_up(M, 8)
    if rows_tile is None:
        rows_tile = onehot_rows_tile(M, chunk)
    rows_tile = min(_round_up(rows_tile, LANES), _round_up(rows, LANES))
    n_cat = math.ceil(rows / rows_tile)
    elt_t = jnp.pad(elt, ((0, n_cat * rows_tile - rows), (0, Mp - M))).T
    col = lambda a: jnp.pad(a, (0, Mp - M)).reshape(Mp, 1)
    out = pl.pallas_call(
        functools.partial(_onehot_kernel, rows_tile=rows_tile, n_cat=n_cat,
                          n_chunks=n_chunks),
        grid=(n_cat, T_pad // tb, n_chunks),
        in_specs=[
            pl.BlockSpec((tb, chunk), lambda r, i, j: (i, j)),
            pl.BlockSpec((Mp, rows_tile), lambda r, i, j: (0, r)),
            pl.BlockSpec((Mp, 1), lambda r, i, j: (0, 0)),
            pl.BlockSpec((Mp, 1), lambda r, i, j: (0, 0)),
            smem,
        ],
        out_specs=pl.BlockSpec((tb, 1), lambda r, i, j: (i, 0)),
        out_shape=out_shape,
        interpret=interpret,
    )(ids, elt_t, col(occ_ret), col(occ_lim), agg)
    return out[:T, 0]
