"""Risk-application driver: run Aggregate Risk Analysis under a tenancy plan.

    PYTHONPATH=src python -m repro.launch.risk --tenants 2 \
        --mode sequential

Prints the YLT risk metrics and the staging/compute timeline, plus the
perf/energy-model prediction for the same deployment (paper Figs 15-22).
``--reduced`` (the default) cuts the workload to CPU-test sizes;
``--no-reduced`` runs the paper's widths (1000 events per trial, 15 ELTs, a
2,000,000-event catalog), with ``--trials`` cutting the trial count.
:func:`build_config` is the piece ``chip_smoke.py`` shares with
:func:`main`.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
from typing import Optional

import jax.numpy as jnp

from repro.configs.risk_app import CONFIG as PAPER_CFG
from repro.configs.risk_app import RiskAppConfig
from repro.core import perfmodel as pm
from repro.core.planner import plan
from repro.launch.compile_cache import enable_compile_cache
from repro.risk import metrics
from repro.risk.analysis import AggregateRiskAnalysis
from repro.risk.tables import generate


def build_config(reduced: bool = True, trials: Optional[int] = None,
                 tenants: int = 2, mode: str = "sequential"
                 ) -> RiskAppConfig:
    cfg = PAPER_CFG.reduced() if reduced else PAPER_CFG
    repl = {"tenants_per_device": tenants, "transfer_mode": mode}
    if trials:
        repl["num_trials"] = trials
    return dataclasses.replace(cfg, **repl)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="cut to CPU-test sizes; --no-reduced runs the "
                         "paper's widths")
    ap.add_argument("--trials", type=int, default=None)
    ap.add_argument("--tenants", type=int, default=2)
    ap.add_argument("--mode", default="sequential",
                    choices=["sequential", "concurrent"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = build_config(args.reduced, args.trials, args.tenants, args.mode)
    tables = generate(cfg, args.seed)
    ara = AggregateRiskAnalysis(cfg)
    rep = ara.run_tenant_chunked(tables)
    print(f"trials={cfg.num_trials} tenants/dev={args.tenants} "
          f"mode={args.mode} wall={rep.wall_s*1e3:.1f} ms")
    for k, v in metrics.summary(jnp.asarray(rep.ylt)).items():
        print(f"  {k:8s} {float(v):,.0f}")

    # model-predicted deployment for the paper-scale workload
    m = pm.PerfModelInputs(net=pm.FDR)
    best = plan(m, "time")
    beste = plan(m, "energy")
    print(f"paper-scale model: time-opt {best.n_pdev}x{best.tenants_per_pdev}"
          f" = {best.exec_time_s:.3f}s | energy-opt "
          f"{beste.n_pdev}x{beste.tenants_per_pdev} = {beste.energy_ws:.0f} Ws")
    return 0


if __name__ == "__main__":
    sys.exit(main())
