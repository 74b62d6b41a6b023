"""Plain Aggregate Risk Analysis (arXiv:1606.04473, Algorithm 3).

For each trial, each event's loss is looked up in each event-loss table,
the occurrence terms clip it (``min(max(l - ret, 0), lim)`` per table), the
clipped losses add up over tables and events to the trial's loss, and the
layer's aggregate terms clip that sum into the year-loss table.

Straight ``jax.numpy`` in the configuration's float32, with no kernel and
no tenancy.  It runs in blocks of trials, and adds the events up a slice at
a time (the paper's "chunking"), so that the looked-up ``(trials, events,
tables)`` losses fit the device.  Event id 0 is the pad event, whose row of
every table is zero.  ``dtype`` exists for the control: the same arithmetic
in bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_TRIALS = 8192
EVENT_SLICE = 128


@functools.partial(jax.jit, static_argnames=("dtype",))
def _trial_losses(ids, elt, occ_ret, occ_lim, dtype):
    elt, ret, lim = elt.astype(dtype), occ_ret.astype(dtype), \
        occ_lim.astype(dtype)
    pad = (-ids.shape[1]) % EVENT_SLICE
    ids = jnp.pad(ids, ((0, 0), (0, pad)))
    slices = ids.reshape(ids.shape[0], -1, EVENT_SLICE).transpose(1, 0, 2)

    def add(total, ids_slice):
        occ = jnp.minimum(jnp.maximum(elt[ids_slice] - ret, 0), lim)
        return total + occ.sum(axis=(1, 2), dtype=dtype), None

    total, _ = jax.lax.scan(add, jnp.zeros(ids.shape[0], dtype), slices)
    return total.astype(jnp.float32)


def trial_losses(yet: np.ndarray, elt: np.ndarray, occ_ret: np.ndarray,
                 occ_lim: np.ndarray, *, dtype=jnp.float32,
                 device=None, block: int = BLOCK_TRIALS) -> np.ndarray:
    """Each trial's loss before the aggregate terms, ``(trials,)``."""
    put = functools.partial(jax.device_put, device=device)
    elt_d, ret_d, lim_d = put(elt), put(occ_ret), put(occ_lim)
    out = []
    with jax.default_matmul_precision("highest"):
        for s in range(0, yet.shape[0], block):
            out.append(_trial_losses(put(yet[s:s + block]), elt_d, ret_d,
                                     lim_d, dtype))
    return np.concatenate([np.asarray(o) for o in out])


def year_losses(trial_loss: np.ndarray, agg_ret: float, agg_lim: float,
                dtype=np.float32) -> np.ndarray:
    """The aggregate terms applied to each trial's loss."""
    x = trial_loss.astype(dtype)
    return np.minimum(np.maximum(x - dtype(agg_ret), 0), dtype(agg_lim)
                      ).astype(np.float32)
