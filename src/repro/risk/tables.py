"""Synthetic YET / ELT / Portfolio generators (paper Section IV-A).

Deterministic (seeded) so tests and benchmarks are reproducible.  The
generator can produce paper-scale data (1M trials x 1000 events, 4 GB packed)
but defaults to reduced sizes; everything is plain numpy on the host — the
pipeline/staging layer owns device placement (that *is* the paper's topic).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple, Union

import numpy as np

from repro.configs.risk_app import RiskAppConfig


@dataclasses.dataclass
class RiskTables:
    """Host-side tables of one layer, or of a portfolio of L layers over
    the same ELTs (a tower), whose terms carry a leading layer axis.

    yet        : (T, K) int32 — per-trial event sequences (0 = pad/no-event)
    elt_losses : (E_cat + 1, M) float32 — direct-access tables, row 0 zero
    occ_ret/occ_lim : (M,) float32 — per-ELT occurrence terms (I); (L, M)
                 for a tower, a zero limit where a layer does not cover an ELT
    agg_ret/agg_lim : float — layer aggregate terms (T); (L,) float32 arrays
                 for a tower
    """
    yet: np.ndarray
    elt_losses: np.ndarray
    occ_ret: np.ndarray
    occ_lim: np.ndarray
    agg_ret: Union[float, np.ndarray]
    agg_lim: Union[float, np.ndarray]

    @property
    def num_trials(self) -> int:
        return self.yet.shape[0]

    @property
    def num_layers(self) -> int:
        return self.occ_ret.shape[0] if self.occ_ret.ndim == 2 else 1

    @property
    def ylt_shape(self) -> Tuple[int, ...]:
        """(T,) for one layer's terms, (T, L) for a tower's."""
        return (self.num_trials,) + self.occ_ret.shape[:-1]

    def nbytes(self) -> Dict[str, int]:
        return {"yet": self.yet.nbytes,
                "elt": self.elt_losses.nbytes,
                "terms": self.occ_ret.nbytes + self.occ_lim.nbytes
                + 16 * self.num_layers}


def generate(cfg: RiskAppConfig, seed: int = 0) -> RiskTables:
    rng = np.random.default_rng(seed)
    T, K, M = cfg.num_trials, cfg.events_per_trial, cfg.num_elts
    cat = cfg.event_catalog

    # Year Event Table: event ids; ~10% pad entries (trials vary in length)
    yet = rng.integers(1, cat + 1, size=(T, K), dtype=np.int64)
    pad = rng.random((T, K)) < 0.1
    yet[pad] = 0
    yet = yet.astype(np.int32)

    # Event Loss Tables: heavy-tailed losses; each ELT covers ~30% of events
    elt = np.zeros((cat + 1, M), np.float32)
    for m in range(M):
        covered = rng.random(cat) < 0.3
        losses = rng.lognormal(mean=10.0, sigma=1.5, size=cat).astype(np.float32)
        elt[1:, m] = np.where(covered, losses, 0.0)

    # financial terms: occurrence retention ~ p25 of losses, limit ~ p99
    nz = elt[elt > 0]
    occ_ret = np.full(M, np.percentile(nz, 25), np.float32) * \
        rng.uniform(0.5, 1.5, M).astype(np.float32)
    occ_lim = np.full(M, np.percentile(nz, 99), np.float32) * \
        rng.uniform(0.5, 1.5, M).astype(np.float32)
    if cfg.num_layers > 1:
        return _tower(yet, elt, occ_ret, cfg.num_layers)
    # aggregate terms scale with expected annual loss
    mean_event = float(nz.mean()) if nz.size else 1.0
    exp_annual = mean_event * K * 0.9 * 0.3 * M   # pads x coverage x ELTs
    agg_ret = 0.1 * exp_annual
    agg_lim = 2.0 * exp_annual
    return RiskTables(yet, elt, occ_ret, occ_lim, float(agg_ret), float(agg_lim))


def _tower(yet, elt, occ_ret, layers: int) -> RiskTables:
    """A catastrophe excess-of-loss tower over the same ELTs: layer l pays
    each event loss's band from 2^l to 2^(l+1) x the one-layer retention
    (the 25th percentile times each ELT's jitter), so the top of 8 layers
    exhausts near the 99.9th percentile; aggregate retention and limit are
    0.1x and 2x the layer's expected annual loss, from the tables
    themselves."""
    growth = 2.0 ** np.arange(layers + 1, dtype=np.float32)
    att = occ_ret[None] * growth[:, None]                  # (L + 1, M)
    occ_ret, occ_lim = att[:-1], att[1:] - att[:-1]
    # a layer's mean loss over the catalog's events, times a trial's events
    per_event = np.array([
        np.minimum(np.clip(elt[1:] - r, 0, None), m).sum(axis=1).mean()
        for r, m in zip(occ_ret, occ_lim)])
    annual = per_event * np.count_nonzero(yet) / yet.shape[0]
    return RiskTables(yet, elt, occ_ret, occ_lim,
                      (0.1 * annual).astype(np.float32),
                      (2.0 * annual).astype(np.float32))


def paper_scale_nbytes(cfg: RiskAppConfig) -> Dict[str, float]:
    """Input footprints in MB for the perf model (paper: YET 4 GB, ELT 120 MB,
    PF 4 MB)."""
    yet_mb = cfg.num_trials * cfg.events_per_trial * 4 / 1e6
    elt_mb = (cfg.event_catalog + 1) * cfg.num_elts * 4 / 1e6
    return {"yet_mb": yet_mb, "elt_mb": elt_mb, "pf_mb": 1.0}
