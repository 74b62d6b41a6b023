"""Driver for the paper's Aggregate Risk Analysis
(``repro.risk.analysis.AggregateRiskAnalysis.run_tenant_chunked``).

Set-up makes one year event table (YET) and one set of event-loss tables
(ELT) from the seed, in one jitted call on the first chip, with the
distributions of ``repro.risk.tables.generate`` (10% pad events, each ELT
covering 30% of the catalog with lognormal losses, occurrence terms at the
25th and 99th loss percentiles times a jitter, aggregate terms a share of
the expected annual loss), and copies them to the host as the program
takes them.  It builds the analysis over the cell's chips, as many as the
configuration's ``devices`` (its deployment's layout), and runs one
analysis to compile the step and make the tables resident on every chip.

The window is a closed loop of whole analyses back to back, each pricing
its own layer terms (the traffic mix scales the aggregate retention and
limit; they are dynamic scalars, so nothing recompiles or re-uploads).  It
starts at the first submission and ends when the first analysis that
finishes after ``--seconds`` finishes: ``trials_per_s`` is every trial of
every analysis over that whole time.  The YET is staged from the host on
every analysis, as the program does.

Once the window has closed, the memory peak read and the program's tables
freed, a plain float32 Algorithm 3 (``bench/reference/risk.py``) computes
each trial's loss once, and every year-loss of every analysis in the window
is compared with it: ``ylt_rel_err`` is the largest gap over the trial's
loss before the aggregate terms, the scale its rounding grows with.
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

# Largest |YLT - reference| / (trial loss) a sound run may show, set from
# readings on the chip (PERF.md): the program's largest over its seeds,
# 4.4e-7 on one chip and 3.7e-7 on four, and the bfloat16 control's smallest,
# 2.4e-4 (tables rounded to bfloat16, float32 arithmetic; 1.2e-2 with the
# arithmetic in bfloat16 too).
YLT_REL_ERR_LIMIT = 3e-5

PROGRAM_FIELDS = ("num_trials", "events_per_trial", "num_elts",
                  "event_catalog", "chunk_events", "tenants_per_device",
                  "transfer_mode", "dtype")


def program_config(conf: Dict[str, Any]):
    from repro.configs.risk_app import RiskAppConfig
    return RiskAppConfig(**{k: conf[k] for k in PROGRAM_FIELDS})


def key_from_seed(seed: int):
    """A JAX key from any whole-number seed, wider ones included."""
    import jax
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0]) >> 1),
                              int(words[1]) >> 1)


def make_tables(conf: Dict[str, Any], seed: int, device):
    """The seed's YET and ELTs, made on ``device`` and copied to the host,
    with its occurrence and base aggregate terms."""
    import jax
    import jax.numpy as jnp
    from repro.risk.tables import RiskTables
    t = conf["tables"]
    T, K = conf["num_trials"], conf["events_per_trial"]
    M, cat = conf["num_elts"], conf["event_catalog"]
    mu, sigma = t["loss_lognormal_mu"], t["loss_lognormal_sigma"]

    def gen(key):
        k_ids, k_pad, k_cov, k_loss, k_jit = jax.random.split(key, 5)
        ids = jax.random.randint(k_ids, (T, K), 1, cat + 1, jnp.int32)
        ids = jnp.where(jax.random.uniform(k_pad, (T, K)) < t["pad_share"],
                        0, ids)
        covered = jax.random.uniform(k_cov, (cat, M)) < t["elt_coverage"]
        loss = jnp.exp(mu + sigma * jax.random.normal(k_loss, (cat, M)))
        elt = jnp.concatenate([jnp.zeros((1, M), jnp.float32),
                               jnp.where(covered, loss, 0.0)])
        jitter = jax.random.uniform(k_jit, (2, M), jnp.float32,
                                    *t["occ_term_jitter"])
        return ids, elt, jitter

    key = jax.device_put(key_from_seed(seed), device)
    ids, elt, jitter = jax.device_get(jax.jit(gen)(key))
    q = lambda p: np.exp(mu + sigma * NormalDist().inv_cdf(p))
    occ_ret = (q(t["occ_ret_quantile"]) * jitter[0]).astype(np.float32)
    occ_lim = (q(t["occ_lim_quantile"]) * jitter[1]).astype(np.float32)
    mean_loss = np.exp(mu + sigma ** 2 / 2)
    annual = mean_loss * K * (1 - t["pad_share"]) * t["elt_coverage"] * M
    tables = RiskTables(np.asarray(ids), np.asarray(elt), occ_ret, occ_lim,
                        0.0, 0.0)
    return tables, (t["agg_ret_of_expected_annual_loss"] * annual,
                    t["agg_lim_of_expected_annual_loss"] * annual)


def priced(tables, base: Tuple[float, float], item: Dict[str, Any]):
    """The same resident tables under this analysis's aggregate terms."""
    return dataclasses.replace(
        tables, agg_ret=float(base[0] * item.get("agg_ret_scale", 1.0)),
        agg_lim=float(base[1] * item.get("agg_lim_scale", 1.0)))


def run(ctx):
    from bench.reference import risk as reference
    from bench.harness import DriverResult
    from bench.traffic import generator
    from repro.risk.analysis import AggregateRiskAnalysis

    conf = ctx.config
    if ctx.traffic["loop"] != "closed" or ctx.traffic.get("clients", 1) != 1:
        raise ValueError("the risk driver runs one closed-loop client")
    if len(ctx.devices) != conf["devices"]:
        raise ValueError(f"the configuration lays the analysis over "
                         f"{conf['devices']} chips, the cell gives "
                         f"{len(ctx.devices)}")
    T = conf["num_trials"]
    t_tables = time.perf_counter()
    tables, base = make_tables(conf, ctx.seed, ctx.devices[0])
    items = generator.draw(ctx.traffic, ctx.seed)
    ara = AggregateRiskAnalysis(program_config(conf), devices=ctx.devices)
    t_warm = time.perf_counter()
    with ctx.span("bench.warmup"):
        ara.run_tenant_chunked(priced(tables, base, {}))
    print(f"bench: set-up before the tables {t_tables - ctx.t_start:.3f} s, "
          f"tables {t_warm - t_tables:.3f} s, warm-up analysis "
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
        if ylt.shape != (T,) or not np.isfinite(ylt).all():
            incomplete += 1
            continue
        want = reference.year_losses(loss, tab.agg_ret, tab.agg_lim)
        err = max(err, float((np.abs(ylt - want) / scale).max()))
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
                  "event_catalog": conf["event_catalog"]})
