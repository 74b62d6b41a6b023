"""Pallas TPU kernel for the Mamba-2 SSD chunked scan (arXiv:2405.21060 §6).

Layout: inputs are flattened to a (B*H, L, ...) head-major layout outside the
kernel; grid = (B*H, L/Q) with the chunk axis innermost and sequential.  The
recurrent state (P x N) lives in a VMEM scratch buffer that persists across
chunk steps of the same head (TPU grid steps run sequentially on a core), so
the inter-chunk recurrence needs no extra HBM round-trips, and Pallas
pipelines the next chunk's HBM->VMEM fetch against the current chunk's
compute — the same DMA/compute overlap the paper exploits via multi-tenancy.

Per chunk (all fp32 in VMEM, contractions at ``Precision.HIGHEST``; the
prefix sum of the log-decays is a triangular matmul):
  intra:  y_d  = ((C B^T) ⊙ L(a)) (dt ⊙ x)        (Q x Q quadratic part)
  carry:  y   += (C h_prev) ⊙ exp(a_cum)
  state:  h    = exp(a_tot) h_prev + B^T ((dt exp(a_tot - a_cum)) ⊙ x)

Validated in interpret mode against kernels.ref.ssd_chunked_ref.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import resolve_interpret


_HI = jax.lax.Precision.HIGHEST


def _dot(a, b, dims):
    """f32 contraction ``dims`` = (lhs contracting, rhs contracting)."""
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def _kernel(x_ref, dtr_ref, dtc_ref, ar_ref, ac_ref, b_ref, c_ref, h0_ref,
            y_ref, hout_ref, state, *, n_chunks: int, has_h0: bool):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        if has_h0:
            state[...] = h0_ref[0].astype(jnp.float32)
        else:
            state[...] = jnp.zeros_like(state)

    x = x_ref[0].astype(jnp.float32)          # (Q, P)
    dt_r, dt_c = dtr_ref[...], dtc_ref[...]   # (1, Q) row and (Q, 1) column
    a_r, a_c = ar_ref[...], ac_ref[...]
    B = b_ref[0].astype(jnp.float32)          # (Q, N)
    C = c_ref[0].astype(jnp.float32)          # (Q, N)
    Q = x.shape[0]

    # inclusive prefix sums of a as a row and as a column, by triangular
    # matmuls (Mosaic has no cumsum): a_cum[l] = sum_{s <= l} a[s]
    li = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = li >= si                                          # [l, s]
    a_cum_c = _dot(causal.astype(jnp.float32), a_c, ((1,), (0,)))   # (Q, 1)
    a_cum_r = _dot(a_r, (li <= si).astype(jnp.float32), ((1,), (0,)))
    a_tot = jnp.sum(a_r, axis=1, keepdims=True)                # (1, 1)

    # intra-chunk: Lmat[l,s] = exp(a_cum[l] - a_cum[s]) for l >= s
    lmat = jnp.where(causal, jnp.exp(a_cum_c - a_cum_r), 0.0)
    cb = _dot(C, B, ((1,), (1,)))                              # (Q, Q)
    y = _dot(cb * lmat * dt_r, x, ((1,), (0,)))                # (Q, P)

    # contribution of the carried state
    h = state[...]                                             # (P, N)
    y += jnp.exp(a_cum_c) * _dot(C, h, ((1,), (1,)))
    y_ref[0] = y.astype(y_ref.dtype)

    # state update
    decay = dt_c * jnp.exp(a_tot - a_cum_c) * B                # (Q, N)
    state[...] = jnp.exp(a_tot) * h + _dot(x, decay, ((0,), (0,)))  # (P, N)

    @pl.when(j == n_chunks - 1)
    def _out():
        hout_ref[0] = state[...]


def ssd_chunked_pallas(x, dt, a_log_decay, B, C, *, chunk: int,
                       initial_state: Optional[jax.Array] = None,
                       interpret: Optional[bool] = None
                       ) -> Tuple[jax.Array, jax.Array]:
    """Same contract as kernels.ref.ssd_chunked_ref.

    x: (b, L, H, P); dt/a: (b, L, H); B/C: (b, L, H, N).
    Returns (y: (b, L, H, P), final_state: (b, H, P, N)).
    ``interpret`` defaults to the platform's choice
    (:func:`repro.kernels.ops.resolve_interpret`).
    """
    b, L, H, P = x.shape
    N = B.shape[-1]
    assert L % chunk == 0, (L, chunk)
    nc = L // chunk
    BH = b * H

    # head-major flatten: (BH, L, ...); dt and a also as (BH, 1, L) rows
    # and (BH, L, 1) columns, so every block is whole (8,128)-rule tiles
    xm = jnp.moveaxis(x, 2, 1).reshape(BH, L, P)
    dtm = jnp.moveaxis(dt.astype(jnp.float32), 2, 1).reshape(BH, L)
    am = jnp.moveaxis(a_log_decay.astype(jnp.float32), 2, 1).reshape(BH, L)
    Bm = jnp.moveaxis(B, 2, 1).reshape(BH, L, N)
    Cm = jnp.moveaxis(C, 2, 1).reshape(BH, L, N)
    has_h0 = initial_state is not None
    h0 = (initial_state.reshape(BH, P, N).astype(jnp.float32)
          if has_h0 else jnp.zeros((BH, P, N), jnp.float32))
    row = pl.BlockSpec((None, 1, chunk), lambda i, j: (i, 0, j))
    col = pl.BlockSpec((None, chunk, 1), lambda i, j: (i, j, 0))

    kernel = functools.partial(_kernel, n_chunks=nc, has_h0=has_h0)
    y, hout = pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda i, j: (i, j, 0)),
            row, col, row, col,
            pl.BlockSpec((1, chunk, N), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, N), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, P, N), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, P, N), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, L, P), x.dtype),
            jax.ShapeDtypeStruct((BH, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(xm, dtm[:, None, :], dtm[:, :, None], am[:, None, :], am[:, :, None],
      Bm, Cm, h0)
    y = jnp.moveaxis(y.reshape(b, H, L, P), 1, 2)
    return y, hout.reshape(b, H, P, N)
