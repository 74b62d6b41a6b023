"""Plain Aggregate Risk Analysis of a portfolio of layers
(arXiv:1606.04473, Algorithm 3 over a portfolio's layers).

A tower's layers cover the same event-loss tables, each under its own
occurrence terms and its own aggregate terms, and the year-loss table holds
one loss per trial and layer.  Each layer is priced on its own by the
one-layer reference, ``bench/reference/risk.py``: straight ``jax.numpy`` in
float32 under ``default_matmul_precision("highest")``, in blocks of trials,
with no kernel and no tenancy.  ``dtype`` exists for the control.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from bench.reference import risk


def trial_losses(yet: np.ndarray, elt: np.ndarray, occ_ret: np.ndarray,
                 occ_lim: np.ndarray, *, dtype=jnp.float32,
                 device=None) -> np.ndarray:
    """Each trial's loss in each layer before the aggregate terms,
    ``(trials, layers)``, from ``(layers, elts)`` occurrence terms."""
    return np.stack([risk.trial_losses(yet, elt, ret, lim, dtype=dtype,
                                       device=device)
                     for ret, lim in zip(occ_ret, occ_lim)], axis=1)


def year_losses(trial_loss: np.ndarray, agg_ret: np.ndarray,
                agg_lim: np.ndarray, dtype=np.float32) -> np.ndarray:
    """Each layer's aggregate terms applied to its column of trial losses."""
    return np.stack([risk.year_losses(trial_loss[:, l], agg_ret[l],
                                      agg_lim[l], dtype=dtype)
                     for l in range(trial_loss.shape[1])], axis=1)
