"""Pallas TPU kernel for Aggregate Risk Analysis (paper Algorithm 3).

TPU adaptation of the paper's GPU kernel.  The GPU version assigns one thread
per trial and reads the ELT direct-access tables from global memory with
per-thread random access, chunking the event axis through shared memory.  A
2,000,000-event catalog of 15 ELTs does not fit the TPU's VMEM, and Mosaic
has no general vector gather, so the lookup strategies below keep the table
in a form the chip can address:

* ``gather`` (default) — one lookup per event.  The occurrence terms are
  per ELT and the layer adds an event's clipped ELT losses, so every call
  first combines them per catalog row, ``f[e] = sum_m min(max(elt[e, m] -
  OccR_m, 0), OccL_m)``, from the ELT and terms it is given (nothing
  derived from them outlives the call), and looks each event up in ``f``
  in one of two ways chosen by shape (:func:`lookup_path`):

  - ``vmem`` — where ``f``'s lane-dense (rows / 128, 128) float32 layout
    fits :data:`LOOKUP_VMEM_BUDGET` (the paper's 2,000,001 rows do), it is
    fetched into VMEM once a call, single-buffered.  Grid = (trial_blocks,
    event_chunks), whole rows of event ids a step where they fit
    :data:`LOOKUP_SMEM_BUDGET`; for each event the kernel reads the id from
    SMEM, loads row ``id >> 7`` of ``f`` with a dynamic sublane load and
    adds lane ``id & 127`` into the trial's lane accumulator (compare with
    a lane iota, select, add).  No DMA per event.
  - ``xla`` — for catalogs too large for that budget: XLA's gather of the
    same ``f`` from HBM, ``f[ids].sum(-1)``, then the aggregate terms.

* ``onehot`` — gather-free: the ELT is transposed to (M, catalog) and tiled
  along the catalog in lane-aligned tiles; each trial's event ids become a
  (rows_tile, C) one-hot matrix that the MXU multiplies by the tile at
  ``Precision.HIGHEST`` (exact: one 1.0 per column).  Every event is
  compared against every catalog row, so the work grows with the catalog;
  it suits small catalogs only.

Both kernels accumulate the YLT block across event chunks (revisiting
output) and apply the layer aggregate terms on the last visit.  They are
validated in interpret mode against
:func:`repro.kernels.ref.aggregate_loss_chunked_ref`
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
# VMEM budget for the gather variant's combined occurrence losses, one float32
# per catalog row: within it the lookup is a VMEM load (the ``vmem`` path),
# beyond it XLA's gather from HBM (``xla``)
LOOKUP_VMEM_BUDGET = 16 << 20
# SMEM budget for one block of event ids on the ``vmem`` path (two are in
# flight, and SMEM holds 1 MiB on a v5e)
LOOKUP_SMEM_BUDGET = 256 << 10
# trials per grid step on the ``vmem`` path, where their ids fit the budget
LOOKUP_TRIAL_BLOCK = 32
# events per iteration of the ``vmem`` path's event loop: on a v5e, 32 took
# 0.279 s a 65,536 x 1,024-event step, 8 0.320 s, 16 0.284 s, 64 0.293 s
LOOKUP_UNROLL = 32


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _aggregate_terms(out_ref, agg_ref):
    """Layer aggregate terms:  min(max(l_T - AggR, 0), AggL)."""
    acc = out_ref[...]
    out_ref[...] = jnp.minimum(jnp.clip(acc - agg_ref[0], 0.0, None),
                               agg_ref[1])


def lookup_path(rows: int) -> str:
    """The ``gather`` variant's lookup for a catalog of ``rows`` rows (pad
    row 0 included): ``"vmem"`` where the combined occurrence losses, laid
    out lane-dense, fit :data:`LOOKUP_VMEM_BUDGET`, else ``"xla"``."""
    vmem_bytes = 4 * _round_up(rows, LANES)
    return "vmem" if vmem_bytes <= LOOKUP_VMEM_BUDGET else "xla"


def _combined_losses(elt, occ_ret, occ_lim):
    """(rows,) occurrence losses per catalog row: per ELT, then over ELTs."""
    return jnp.minimum(jnp.clip(elt - occ_ret[None], 0.0, None),
                       occ_lim[None]).sum(axis=1)


def _vmem_kernel(ids_ref, f_ref, agg_ref, out_ref, acc_ref, *,
                 n_chunks: int, unroll: int):
    j = pl.program_id(1)
    tb, c = ids_ref.shape

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def trial(t, carry):
        def events(k, acc):        # Mosaic unrolls by hand only
            for u in range(unroll):
                i = ids_ref[t, k * unroll + u]
                row = f_ref[pl.ds(i >> 7, 1), :]        # i // LANES
                acc = acc + jnp.where(lane == (i & (LANES - 1)), row, 0.0)
            return acc

        acc_ref[pl.ds(t, 1), :] = jax.lax.fori_loop(
            0, c // unroll, events, acc_ref[pl.ds(t, 1), :])
        return carry

    jax.lax.fori_loop(0, tb, trial, 0)

    @pl.when(j == n_chunks - 1)
    def _agg():
        out_ref[...] = acc_ref[...].sum(axis=1, keepdims=True)
        _aggregate_terms(out_ref, agg_ref)


def _vmem_lookup(ids, f, agg, *, chunk: int, trial_block: Optional[int],
                 interpret: bool):
    """The ``vmem`` path of the gather variant: ``ids`` is (T, K) with K a
    multiple of ``chunk``, ``f`` the (rows,) combined occurrence losses.
    Returns the (T,) YLT."""
    T, K = ids.shape
    rows = f.shape[0]
    R = -(-rows // LANES)
    f = jnp.pad(f, (0, R * LANES - rows)).reshape(R, LANES)
    # whole rows of ids a step where eight trials' fit the SMEM budget
    c = K if 4 * 8 * K <= LOOKUP_SMEM_BUDGET else chunk
    tb = trial_block or max(8, min(LOOKUP_TRIAL_BLOCK,
                                   LOOKUP_SMEM_BUDGET // (4 * c) // 8 * 8))
    tb = min(_round_up(tb, 8), _round_up(T, 8))
    T_pad = _round_up(T, tb)
    if T_pad != T:
        ids = jnp.pad(ids, ((0, T_pad - T), (0, 0)))
    n_chunks = K // c
    f_bytes = 4 * R * LANES
    out = pl.pallas_call(
        functools.partial(_vmem_kernel, n_chunks=n_chunks,
                          unroll=math.gcd(c, LOOKUP_UNROLL)),
        grid=(T_pad // tb, n_chunks),
        in_specs=[
            pl.BlockSpec((tb, c), lambda i, j: (i, j),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((R, LANES), lambda i, j: (0, 0),
                         pipeline_mode=pl.Buffered(1)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((tb, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T_pad, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((tb, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=f_bytes + (8 << 20)),
        interpret=interpret,
    )(ids, f, agg)
    return out[:T, 0]


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
                          trial_block: Optional[int] = None,
                          rows_tile: Optional[int] = None,
                          interpret: Optional[bool] = None,
                          variant: str = "gather"):
    """Drop-in equivalent of kernels.ref.aggregate_loss_chunked_ref.

    ``variant``: "gather" (one lookup per event of the combined occurrence
    losses: from VMEM, or by XLA's gather where the catalog is too large,
    see :func:`lookup_path`) or "onehot" (one-hot x catalog-tile matmul on
    the MXU; ``rows_tile`` overrides its tile, rounded up to a multiple of
    128).  ``trial_block`` overrides the trials a grid step prices (8 on
    the onehot path, up to :data:`LOOKUP_TRIAL_BLOCK` on the VMEM path).  ``interpret`` defaults to the platform's choice
    (:func:`repro.kernels.ops.resolve_interpret`)."""
    if variant not in VARIANTS:
        raise ValueError(f"variant {variant!r}: must be one of {VARIANTS}")
    interpret = resolve_interpret(interpret)
    T, K = event_ids.shape
    rows, M = elt_losses.shape
    chunk = min(chunk, K)
    while K % chunk:
        chunk //= 2
    ids = event_ids.astype(jnp.int32)
    elt = elt_losses.astype(jnp.float32)
    occ_ret = occ_ret.astype(jnp.float32)
    occ_lim = occ_lim.astype(jnp.float32)
    agg = jnp.stack([jnp.asarray(agg_ret, jnp.float32),
                     jnp.asarray(agg_lim, jnp.float32)])
    if variant == "gather":
        f = _combined_losses(elt, occ_ret, occ_lim)
        if lookup_path(rows) == "vmem":
            return _vmem_lookup(ids, f, agg, chunk=chunk,
                                trial_block=trial_block, interpret=interpret)
        y = f[ids].sum(axis=1)
        return jnp.minimum(jnp.clip(y - agg[0], 0.0, None), agg[1])

    # the (tb, 1) YLT block needs tb % 8 == 0 (or the whole axis): pad the
    # trial axis with all-pad trials, sliced off below
    tb = min(_round_up(trial_block or 8, 8), _round_up(T, 8))
    T_pad = _round_up(T, tb)
    if T_pad != T:
        ids = jnp.pad(ids, ((0, T_pad - T), (0, 0)))
    n_chunks = K // chunk
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
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((tb, 1), lambda r, i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T_pad, 1), jnp.float32),
        interpret=interpret,
    )(ids, elt_t, col(occ_ret), col(occ_lim), agg)
    return out[:T, 0]
