"""Driver for a portfolio of layers priced by the paper's Aggregate Risk
Analysis (``repro.risk.analysis.AggregateRiskAnalysis.run_tenant_chunked``
with per-layer terms): a catastrophe excess-of-loss tower whose layers
cover the same event-loss tables (ELT).

Set-up makes the seed's year event table (YET) and ELTs as the one-layer
driver does (``bench/drivers/risk.py``, whose helpers this reuses), then
stacks the tower on them: layer l pays each event loss's band between
``A x g^l`` and ``A x g^(l+1)`` on each ELT, ``A`` the one-layer
configuration's occurrence retention (a loss quantile times the ELT's
jitter) and ``g`` the configuration's ``layer_growth``; each layer's
aggregate retention and limit are shares of its expected annual loss,
worked from the loss distribution.  It builds the analysis and runs
``WARMUP_ANALYSES`` analyses: the first compiles the step and makes the
tables resident, the rest price the traffic's first draws.

The window is the one-layer driver's closed loop of whole analyses, each
scaling every layer's aggregate terms by the traffic mix's draws (dynamic
``(L,)`` arguments, so nothing recompiles or re-uploads).  Once the window
has closed, a plain float32 Algorithm 3 over each layer
(``bench/reference/risk_tower.py``) computes each trial's loss in each
layer, and every year-loss of every layer of every analysis is compared
with it: ``ylt_rel_err`` is the largest gap over that layer's trial loss
before the aggregate terms (at least 1).  The program must carry the layer
axis; the driver checks that before it makes anything.
"""
from __future__ import annotations

import dataclasses
import gc
import itertools
import sys
import time
from statistics import NormalDist
from typing import Any, Dict, Tuple

import numpy as np

from bench.drivers import risk as one_layer

YLT_REL_ERR_LIMIT = one_layer.YLT_REL_ERR_LIMIT

# Analyses before the window.  On a v5e, runs that loaded the step from the
# compile cache opened their window ~10 s sooner after start-up than runs
# that compiled it, and ran slower and less steadily (PERF.md, Findings);
# the further analyses (~9 s) hold every run's window to a steady host.
WARMUP_ANALYSES = 16


def expected_excess(mu: float, sigma: float, k: np.ndarray) -> np.ndarray:
    """E[max(X - k, 0)] for a lognormal X of parameters ``mu``, ``sigma``."""
    cdf = np.vectorize(NormalDist().cdf)
    d2 = (mu - np.log(k)) / sigma
    return np.exp(mu + sigma ** 2 / 2) * cdf(d2 + sigma) - k * cdf(d2)


def tower_terms(conf: Dict[str, Any], occ_ret: np.ndarray):
    """The tower's ``(L, M)`` occurrence terms over the one-layer retention
    ``occ_ret`` and its base ``(L,)`` aggregate terms."""
    t, L = conf["tables"], conf["num_layers"]
    mu, sigma = t["loss_lognormal_mu"], t["loss_lognormal_sigma"]
    att = occ_ret[None].astype(np.float64) * \
        t["layer_growth"] ** np.arange(L + 1)[:, None]      # (L + 1, M)
    ret, lim = att[:-1], att[1:] - att[:-1]
    events = conf["events_per_trial"] * (1 - t["pad_share"]) * \
        t["elt_coverage"]
    annual = events * (expected_excess(mu, sigma, ret)
                       - expected_excess(mu, sigma, ret + lim)).sum(axis=1)
    f32 = lambda a: np.asarray(a, np.float32)
    return (f32(ret), f32(lim)), (
        f32(t["agg_ret_of_expected_annual_loss"] * annual),
        f32(t["agg_lim_of_expected_annual_loss"] * annual))


def make_tables(conf: Dict[str, Any], seed: int, device):
    """The seed's YET and ELTs with the tower's terms, and its base
    aggregate terms."""
    tables, _ = one_layer.make_tables(conf, seed, device)
    (ret, lim), base = tower_terms(conf, tables.occ_ret)
    return dataclasses.replace(tables, occ_ret=ret, occ_lim=lim,
                               agg_ret=base[0], agg_lim=base[1]), base


def priced(tables, base: Tuple[np.ndarray, np.ndarray], item: Dict[str, Any]):
    """The same resident tables under this analysis's aggregate terms."""
    s_ret = np.float32(item.get("agg_ret_scale", 1.0))
    s_lim = np.float32(item.get("agg_lim_scale", 1.0))
    return dataclasses.replace(tables, agg_ret=base[0] * s_ret,
                               agg_lim=base[1] * s_lim)


def run(ctx):
    from bench.harness import DriverResult
    from bench.reference import risk_tower as reference
    from bench.traffic import generator
    from repro.risk.analysis import AggregateRiskAnalysis
    from repro.risk.tables import RiskTables

    conf = ctx.config
    if not hasattr(RiskTables, "num_layers"):
        raise RuntimeError("the program prices one layer only: its "
                           "RiskTables has no layer axis")
    if ctx.traffic["loop"] != "closed" or ctx.traffic.get("clients", 1) != 1:
        raise ValueError("the risk driver runs one closed-loop client")
    if len(ctx.devices) != conf["devices"]:
        raise ValueError(f"the configuration lays the analysis over "
                         f"{conf['devices']} chips, the cell gives "
                         f"{len(ctx.devices)}")
    T, L = conf["num_trials"], conf["num_layers"]
    t_tables = time.perf_counter()
    tables, base = make_tables(conf, ctx.seed, ctx.devices[0])
    items = generator.draw(ctx.traffic, ctx.seed)
    ara = AggregateRiskAnalysis(
        dataclasses.replace(one_layer.program_config(conf), num_layers=L),
        devices=ctx.devices)
    t_warm = time.perf_counter()
    with ctx.span("bench.warmup"):
        for item in [{}] + items[:WARMUP_ANALYSES - 1]:
            ara.run_tenant_chunked(priced(tables, base, item))
    print(f"bench: set-up before the tables {t_tables - ctx.t_start:.3f} s, "
          f"tables {t_warm - t_tables:.3f} s, warm-up analyses "
          f"{time.perf_counter() - t_warm:.3f} s", file=sys.stderr)

    done = []
    t0 = ctx.start_window()
    for item in itertools.cycle(items):
        tab = priced(tables, base, item)
        with ctx.span("bench.analysis"):
            rep = ara.run_tenant_chunked(tab)
        done.append((tab, rep.ylt))
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    elapsed = time.perf_counter() - t0
    ctx.end_window()
    t_ref = time.perf_counter()
    calls = ara.tenancy.n_pdev * ara.tenancy.tenants_per_pdev
    ara.clear_table_cache()
    del ara
    gc.collect()

    loss = reference.trial_losses(tables.yet, tables.elt_losses,
                                  tables.occ_ret, tables.occ_lim,
                                  device=ctx.devices[0])
    scale = np.maximum(loss, 1.0)
    err, incomplete = 0.0, 0
    for tab, ylt in done:
        ylt = np.asarray(ylt)
        if ylt.shape != (T, L) or not np.isfinite(ylt).all():
            incomplete += 1
            continue
        want = reference.year_losses(loss, tab.agg_ret, tab.agg_lim)
        err = max(err, float((np.abs(ylt - want) / scale).max()))
    want = reference.year_losses(loss, base[0], base[1])
    print("bench: per layer at the base terms, share of trials with a loss "
          f"{(want > 0).mean(axis=0).round(6).tolist()}, at the limit "
          f"{(want >= base[1]).mean(axis=0).round(6).tolist()}",
          file=sys.stderr)
    print(f"bench: reference and comparison {time.perf_counter() - t_ref:.3f} s",
          file=sys.stderr)
    return DriverResult(
        attempted=len(done), failed=incomplete,
        end_to_end={"trials_per_s": len(done) * T / elapsed},
        checks={"ylt_rel_err": (err, YLT_REL_ERR_LIMIT),
                "analyses_incomplete": (incomplete, 0)},
        counters={"analyses": len(done), "step_calls_per_analysis": calls,
                  "trials_per_step": T / calls,
                  "events_per_trial": conf["events_per_trial"],
                  "num_elts": conf["num_elts"],
                  "event_catalog": conf["event_catalog"],
                  "num_layers": L})
