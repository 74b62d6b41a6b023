"""Pallas TPU kernel for Aggregate Risk Analysis (paper Algorithm 3).

TPU adaptation of the paper's GPU kernel.  The GPU version assigns one thread
per trial and reads the ELT direct-access tables from global memory with
per-thread random access, chunking the event axis through shared memory.  A
2,000,000-event catalog of 15 ELTs does not fit the TPU's VMEM, and Mosaic
has no general vector gather, so the lookup below keeps the table in a form
the chip can address.

A call prices one layer, or a portfolio of L layers over the same ELTs
(a reinsurance tower): occurrence terms ``(M,)`` and scalar aggregate terms
give a ``(T,)`` year-loss table (YLT), terms ``(L, M)`` and ``(L,)`` a
``(T, L)`` one.  A layer that does not cover an ELT has a zero occurrence
limit on it.

The lookup, ``gather``, reads one combined loss per event.  The occurrence
terms are per ELT and a layer adds an event's clipped ELT losses, so every
call first combines them per catalog row and layer, ``f[e, l] = sum_m
min(max(elt[e, m] - OccR_lm, 0), OccL_lm)``, from the ELT and terms it is
given (nothing derived from them outlives the call), and looks each event
up in ``f`` in one of two ways chosen by shape (:func:`lookup_path`):

* ``vmem`` — where ``f``, laid out lane-dense, fits
  :data:`LOOKUP_VMEM_BUDGET` (the paper's 2,000,001 rows do, for one
  layer in 8 MB and for eight in 64 MiB), it is fetched into VMEM once a
  call, single-buffered.  Grid = (trial_blocks, event_chunks), a
  trial's whole event ids a step where they fit
  :data:`LOOKUP_SMEM_BUDGET`.  The ids reach SMEM in rows of 128 events
  (of K where K is under 128), the (8, 128) tiles that (T, K) has in
  HBM, so no copy makes them; the kernel walks a trial's rows and
  unrolls each row, so an id's SMEM address is its row's plus a
  constant.  For each event it loads the id, loads the event's row of
  the table with one dynamic sublane load and adds the event's lanes
  into the trial's lane accumulator (the id broadcast over the lanes,
  its slot compared with a lane iota, select, add).  Besides the id's
  load, an event takes three scalar operations (the id's address, the
  table row, the row's address); the rest is vector work, and the adds
  into one accumulator are the chain that bounds the loop.  No DMA per
  event.  One layer's table is (rows / 128, 128): row ``id >> 7``, lane
  ``id & 127``.  A tower's layers are padded to a power of two, Lp, and
  interleaved so that one load brings every layer of an event: a (R,
  128) table, R a power of two of at least rows / G with G = 128 / Lp,
  catalog row ``id`` at row ``id & (R - 1)``, lanes ``(id >> log2 R) *
  Lp + l`` for its layers ``l``
  (:func:`_tower_table`; built by a 2-D transpose of the layer-major
  combine).  The last chunk adds the accumulator's G lane groups into
  the trial's Lp layer losses, by rolls.
* ``xla`` — for tables too large for that budget: XLA's gather of the
  same ``f`` from HBM, then the aggregate terms.

The ``vmem`` kernel accumulates the YLT block across event chunks and
applies each layer's aggregate terms on the last visit.  Both paths are
validated on the CPU (the kernel in interpret mode) against
:func:`repro.kernels.ref.aggregate_loss_chunked_ref`
(tests/test_kernels_aggregate.py) and compiled for a described v5e at the
paper's widths (tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import resolve_interpret

LANES = 128
# VMEM budget for the lookup's combined occurrence losses, one float32
# per catalog row and padded layer: within it the lookup is a VMEM load (the
# ``vmem`` path), beyond it XLA's gather from HBM (``xla``).  A v5e has
# 128 MiB of VMEM; the call asks for the table plus 8 MiB
LOOKUP_VMEM_BUDGET = 96 << 20
# SMEM budget for one block of event ids on the ``vmem`` path (two are in
# flight, and SMEM holds 1 MiB on a v5e)
LOOKUP_SMEM_BUDGET = 256 << 10
# trials per grid step on the ``vmem`` path, where their ids fit the budget
LOOKUP_TRIAL_BLOCK = 32


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _aggregate_terms(out_ref, agg_ref):
    """Layer aggregate terms:  min(max(l_T - AggR, 0), AggL)."""
    acc = out_ref[...]
    out_ref[...] = jnp.minimum(jnp.clip(acc - agg_ref[0], 0.0, None),
                               agg_ref[1])


def padded_layers(layers: int) -> int:
    """Layers as the ``vmem`` table holds them: the next power of two."""
    if not 1 <= layers <= LANES:
        raise ValueError(f"{layers} layers: the lookup prices 1 to {LANES}")
    return 1 << (layers - 1).bit_length()


def table_rows(rows: int, layers: int = 1) -> int:
    """Rows of 128 lanes the ``vmem`` table of a ``rows``-row catalog takes:
    one layer packs 128 catalog rows a table row; a tower packs 128 / Lp
    catalog rows into a power-of-two count of table rows."""
    per_row = LANES // padded_layers(layers)
    if layers == 1:
        return -(-rows // per_row)
    return 1 << (-(-rows // per_row) - 1).bit_length()


def lookup_path(rows: int, layers: int = 1) -> str:
    """The lookup's path for a catalog of ``rows`` rows (pad row 0
    included) priced for ``layers`` layers: ``"vmem"`` where the combined
    occurrence losses, laid out lane-dense (:func:`table_rows` x 128
    float32), fit :data:`LOOKUP_VMEM_BUDGET`, else ``"xla"``."""
    vmem_bytes = 4 * LANES * table_rows(rows, layers)
    return "vmem" if vmem_bytes <= LOOKUP_VMEM_BUDGET else "xla"


def _combined_losses(elt, occ_ret, occ_lim):
    """Occurrence losses per catalog row, per ELT and then over ELTs:
    ``(rows,)`` for terms ``(M,)``; layer-major ``(L, rows)`` for terms
    ``(L, M)``, so that the catalog stays on the lanes."""
    if occ_ret.ndim == 2:
        return jnp.minimum(jnp.clip(elt.T[None] - occ_ret[..., None], 0.0,
                                    None), occ_lim[..., None]).sum(axis=1)
    return jnp.minimum(jnp.clip(elt - occ_ret[None], 0.0, None),
                       occ_lim[None]).sum(axis=1)


def _tower_table(f, rows_out: int):
    """The ``vmem`` table of L layers' (L, rows) combined losses: Lp =
    :func:`padded_layers`, G = 128 / Lp catalog rows a table row, and
    catalog row ``q * rows_out + r``'s layer ``l`` at row ``r``, lane ``q *
    Lp + l`` (zero layers and rows pad it)."""
    layers, rows = f.shape
    lp = padded_layers(layers)
    f = jnp.pad(f, ((0, lp - layers), (0, LANES // lp * rows_out - rows)))
    f = f.reshape(lp, LANES // lp, rows_out).transpose(1, 0, 2)
    return f.reshape(LANES, rows_out).T


def _vmem_kernel(ids_ref, f_ref, agg_ref, out_ref, acc_ref, *,
                 n_chunks: int, layers: int):
    j = pl.program_id(1)
    tb = acc_ref.shape[0]
    n_rows, width = ids_ref.shape[1], ids_ref.shape[3]  # a trial's ids' rows

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # the row's address is scalar work; the slot is taken on the vector
    # unit from the id broadcast over the lanes
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    if layers == 1:     # row i >> 7, lane i & 127
        row_of = lambda i: i >> 7
        slot_of = lambda i: i & (LANES - 1)
    else:               # row i & (R - 1), lanes of slot i >> log2(R)
        r_bits = f_ref.shape[0].bit_length() - 1
        row_of = lambda i: i & (f_ref.shape[0] - 1)
        slot_of = lambda i: i >> r_bits
        lane = lane >> (layers.bit_length() - 1)    # the slot of each lane

    def trial(t, carry):
        def events(q, acc):     # one row of ids, its columns static
            for u in range(width):
                i = ids_ref[t >> 3, q, t & 7, u]
                row = f_ref[pl.ds(row_of(i), 1), :]
                slot = slot_of(jnp.broadcast_to(i, lane.shape))
                acc = acc + jnp.where(lane == slot, row, 0.0)
            return acc

        acc_ref[pl.ds(t, 1), :] = jax.lax.fori_loop(
            0, n_rows, events, acc_ref[pl.ds(t, 1), :])
        return carry

    jax.lax.fori_loop(0, tb, trial, 0)

    @pl.when(j == n_chunks - 1)
    def _agg():
        if layers == 1:
            out_ref[...] = acc_ref[...].sum(axis=1, keepdims=True)
            _aggregate_terms(out_ref, agg_ref)
            return
        # add the slots up: after rolls by 64, 32, ..., Lp (either way
        # round) every lane holds the sum of the lanes of its layer
        acc = acc_ref[...]
        shift = LANES // 2
        while shift >= layers:
            acc = acc + pltpu.roll(acc, shift, 1)
            shift //= 2
        out_ref[...] = jnp.minimum(
            jnp.clip(acc[:, :layers] - agg_ref[0:1, :], 0.0, None),
            agg_ref[1:2, :])


def _vmem_lookup(ids, f, agg, *, trial_block: Optional[int],
                 interpret: bool):
    """The ``vmem`` path of the lookup: ``ids`` is (T, K); ``f``
    the (rows,) combined occurrence losses and ``agg`` the (2,) aggregate
    terms of one layer, or ``f`` (L, rows) and ``agg`` (2, L) for L layers.
    Returns the (T,) or (T, L) YLT."""
    T, K = ids.shape
    layered = f.ndim == 2
    layers, rows = f.shape if layered else (1, f.shape[0])
    lp = padded_layers(layers)
    R = table_rows(rows, layers)
    if layered:     # pad layers price nothing and are sliced off below
        f = _tower_table(f, R)
        agg = jnp.pad(agg, ((0, 0), (0, lp - layers)))
    else:
        f = jnp.pad(f, (0, R * LANES - rows)).reshape(R, LANES)
    # events in rows of 128, or of K under 128 (pad event 0 prices
    # nothing), in the order of the (8, 128) tiles of (T, K), which is how
    # (T, K) lies in HBM: ids[a, q, r] is trial 8a + r's row q.  A step
    # takes its trials' whole rows where eight trials' fit the SMEM budget,
    # else one row a trial
    width = min(K, LANES)
    k_rows = -(-K // width)
    c_rows = k_rows if 4 * 8 * LANES * k_rows <= LOOKUP_SMEM_BUDGET else 1
    tb = trial_block or max(8, min(LOOKUP_TRIAL_BLOCK, LOOKUP_SMEM_BUDGET
                                   // (4 * LANES * c_rows) // 8 * 8))
    tb = min(_round_up(tb, 8), _round_up(T, 8))
    T_pad = _round_up(T, tb)
    ids = jnp.pad(ids, ((0, T_pad - T), (0, width * k_rows - K)))
    ids = ids.reshape(T_pad // 8, 8, k_rows, width).transpose(0, 2, 1, 3)
    n_chunks = k_rows // c_rows
    f_bytes = 4 * R * LANES
    # one layer's terms are SMEM scalars; a tower's a (2, Lp) VMEM block
    agg_spec = (pl.BlockSpec((2, lp), lambda i, j: (0, 0)) if layered else
                pl.BlockSpec(memory_space=pltpu.SMEM))
    out = pl.pallas_call(
        functools.partial(_vmem_kernel, n_chunks=n_chunks, layers=lp),
        grid=(T_pad // tb, n_chunks),
        in_specs=[
            pl.BlockSpec((tb // 8, c_rows, 8, width),
                         lambda i, j: (i, j, 0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((R, LANES), lambda i, j: (0, 0),
                         pipeline_mode=pl.Buffered(1)),
            agg_spec,
        ],
        out_specs=pl.BlockSpec((tb, lp), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T_pad, lp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((tb, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=f_bytes + (8 << 20)),
        interpret=interpret,
    )(ids, f, agg)
    return out[:T, :layers] if layered else out[:T, 0]


def aggregate_loss_pallas(event_ids, elt_losses, occ_ret, occ_lim, agg_ret,
                          agg_lim, *, trial_block: Optional[int] = None,
                          interpret: Optional[bool] = None):
    """Drop-in equivalent of kernels.ref.aggregate_loss_chunked_ref: one
    layer's terms, ``(M,)`` and scalars, give the ``(T,)`` YLT; L layers'
    terms, ``(L, M)`` and ``(L,)``, the ``(T, L)`` YLT.

    Every event is looked up once in the combined occurrence losses, every
    layer at once: from VMEM, or by XLA's gather where the table is too
    large (:func:`lookup_path`).  ``trial_block`` overrides the trials a
    grid step of the VMEM lookup prices (up to
    :data:`LOOKUP_TRIAL_BLOCK`).  ``interpret`` defaults to the platform's
    choice (:func:`repro.kernels.ops.resolve_interpret`).
    """
    interpret = resolve_interpret(interpret)
    if jnp.ndim(occ_ret) == 2:
        layers = occ_ret.shape[0]
        if jnp.shape(agg_ret) != (layers,) or jnp.shape(agg_lim) != (layers,):
            raise ValueError(f"{layers} layers' occurrence terms need "
                             f"({layers},) aggregate terms, not "
                             f"{jnp.shape(agg_ret)} and {jnp.shape(agg_lim)}")
        if layers == 1:     # one layer is the one-layer program
            y = aggregate_loss_pallas(
                event_ids, elt_losses, occ_ret[0], occ_lim[0], agg_ret[0],
                agg_lim[0], trial_block=trial_block, interpret=interpret)
            return y[:, None]
    rows = elt_losses.shape[0]
    ids = event_ids.astype(jnp.int32)
    elt = elt_losses.astype(jnp.float32)
    occ_ret = occ_ret.astype(jnp.float32)
    occ_lim = occ_lim.astype(jnp.float32)
    agg = jnp.stack([jnp.asarray(agg_ret, jnp.float32),
                     jnp.asarray(agg_lim, jnp.float32)])
    f = _combined_losses(elt, occ_ret, occ_lim)
    layers = f.shape[0] if f.ndim == 2 else 1
    if lookup_path(rows, layers) == "vmem":
        return _vmem_lookup(ids, f, agg, trial_block=trial_block,
                            interpret=interpret)
    y = f.T[ids].sum(axis=1)
    return jnp.minimum(jnp.clip(y - agg[0], 0.0, None), agg[1])
