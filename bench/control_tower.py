#!/usr/bin/env python3
"""Readings that the tower cell's ``ylt_rel_err`` limit is checked against.

    python3 bench/control_tower.py --seeds 11 12 13 --analyses 2

``bench/control.py`` for the tower (``bench/configs/risk-tower8.json``):
for each seed, in one process on the chip, at the cell's own sizes, the
seed's tables and the float32 reference's per-layer trial losses, then

* ``program``: ``ylt_rel_err`` over every layer of ``--analyses`` analyses
  run through ``AggregateRiskAnalysis.run_tenant_chunked``, priced with the
  traffic mix's first terms, as a benchmark run compares them;
* ``control_bf16``: the reference itself in the program's place, computed
  in bfloat16 (the precision below the configuration's float32);
* ``control_bf16_tables``: the reference in float32 over tables rounded to
  bfloat16.

Each line printed is one JSON object of a seed's readings.  The benchmark's
own runs never run this.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--analyses", type=int, default=2)
    ap.add_argument("--config", default="risk-tower8")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax.numpy as jnp
    import numpy as np
    from bench import harness
    from bench.control import rel_err
    from bench.drivers import risk_tower as driver
    from bench.reference import risk_tower as reference
    from bench.traffic import generator
    from repro.risk.analysis import AggregateRiskAnalysis
    conf = json.loads((ROOT / "bench" / "configs" /
                       f"{args.config}.json").read_text())
    devices = harness.chip_devices(conf["devices"])
    harness.enable_compile_cache(ROOT)
    traffic = generator.load("risk-analyses")
    ara = AggregateRiskAnalysis(
        dataclasses.replace(driver.one_layer.program_config(conf),
                            num_layers=conf["num_layers"]), devices=devices)
    bf16 = lambda a: np.asarray(a).astype(jnp.bfloat16).astype(np.float32)
    for seed in args.seeds:
        t0 = time.perf_counter()
        tables, base = driver.make_tables(conf, seed, devices[0])
        items = generator.draw(traffic, seed)[:max(args.analyses, 1)]
        priced = [driver.priced(tables, base, it) for it in items]
        ylts = [ara.run_tenant_chunked(p).ylt for p in priced[:args.analyses]]
        ara.clear_table_cache()
        t1 = time.perf_counter()
        terms = (tables.yet, tables.elt_losses, tables.occ_ret,
                 tables.occ_lim)
        loss = reference.trial_losses(*terms, device=devices[0])
        t2 = time.perf_counter()
        half = reference.trial_losses(*terms, dtype=jnp.bfloat16,
                                      device=devices[0])
        tab16 = reference.trial_losses(tables.yet, *map(bf16, terms[1:]),
                                       device=devices[0])
        want = [reference.year_losses(loss, p.agg_ret, p.agg_lim)
                for p in priced]
        out = {
            "seed": seed,
            "program": max((rel_err(y, w, loss) for y, w in zip(ylts, want)),
                           default=None),
            "control_bf16": max(rel_err(
                reference.year_losses(half, p.agg_ret, p.agg_lim,
                                      dtype=jnp.bfloat16), w, loss)
                for p, w in zip(priced, want)),
            "control_bf16_tables": max(rel_err(
                reference.year_losses(tab16, p.agg_ret, p.agg_lim), w, loss)
                for p, w in zip(priced, want)),
            "share_with_loss": (want[0] > 0).mean(axis=0).round(6).tolist(),
            "share_at_limit": (want[0] >= priced[0].agg_lim).mean(
                axis=0).round(6).tolist(),
            "program_s": t1 - t0, "reference_s": t2 - t1,
        }
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
