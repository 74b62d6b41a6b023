#!/usr/bin/env python3
"""Readings that the risk cells' ``ylt_rel_err`` limit is set from.

    python3 bench/control.py --seeds 11 12 13 --analyses 2 [--config risk-paper]

For each seed, in one process on the chip, at the cell's own sizes: the
seed's tables, the float32 reference's trial losses, and then

* ``program``: ``ylt_rel_err`` of ``--analyses`` analyses run through
  ``AggregateRiskAnalysis.run_tenant_chunked`` on the configuration's
  ``devices`` chips, priced with the traffic mix's first terms, as a
  benchmark run compares them;
* ``control_bf16``: the reference itself in the program's place, computed
  in bfloat16 (the precision below the configuration's float32);
* ``control_bf16_tables``: the reference in float32 over tables rounded to
  bfloat16, the step a later change would be tempted to take.

Each line printed is one JSON object of a seed's readings.  The benchmark's
own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent


def rel_err(ylt, want, loss):
    import numpy as np
    return float((np.abs(ylt - want) / np.maximum(loss, 1.0)).max())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--analyses", type=int, default=2)
    ap.add_argument("--config", default="risk-paper")
    ap.add_argument("--traffic", default="risk-analyses")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench import harness
    from bench.reference import risk as reference
    from bench.traffic import generator
    from repro.risk.analysis import AggregateRiskAnalysis
    driver = harness.load_module(ROOT / "bench" / "drivers" / "risk.py",
                                 "driver")
    conf = json.loads((ROOT / "bench" / "configs" /
                       f"{args.config}.json").read_text())
    devices = harness.chip_devices(conf["devices"])
    harness.enable_compile_cache(ROOT)
    traffic = generator.load(args.traffic)
    ara = AggregateRiskAnalysis(driver.program_config(conf), devices=devices)
    for seed in args.seeds:
        t0 = time.perf_counter()
        tables, base = driver.make_tables(conf, seed, devices[0])
        items = generator.draw(traffic, seed)[:max(args.analyses, 1)]
        priced = [driver.priced(tables, base, it) for it in items]
        ylts = [ara.run_tenant_chunked(p).ylt for p in priced[:args.analyses]]
        ara.clear_table_cache()
        t1 = time.perf_counter()
        loss = reference.trial_losses(tables.yet, tables.elt_losses,
                                      tables.occ_ret, tables.occ_lim,
                                      device=devices[0])
        t2 = time.perf_counter()
        half = reference.trial_losses(tables.yet, tables.elt_losses,
                                      tables.occ_ret, tables.occ_lim,
                                      dtype=jnp.bfloat16, device=devices[0])
        rounded = [a.astype(jnp.bfloat16).astype(np.float32) for a in
                   (tables.elt_losses, tables.occ_ret, tables.occ_lim)]
        tab16 = reference.trial_losses(tables.yet, *rounded,
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
            "program_s": t1 - t0, "reference_s": t2 - t1,
        }
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
