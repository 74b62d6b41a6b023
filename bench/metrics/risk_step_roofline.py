"""``risk_step_roofline``: the least time the chip could take for the risk
analysis steps in the traced window, over the device time of those steps,
in %.

The steps are the runs of the compiled analysis step program
(``AggregateRiskAnalysis._trial_losses``, traced as ``jit__trial_losses``)
on every chip.  Each run's least time is the larger of its operations over
the bf16 peak and its bytes over the HBM peak, counted by
``bench.counts.risk_step_work`` for the trials it prices; the bytes bound
it at the paper's widths.  Returns nothing where the trace holds no run.
"""
from bench import counts, traces

PROGRAM = "jit__trial_losses"


def read(ctx):
    runs = traces.module_runs(ctx.trace, PROGRAM)
    if not runs:
        return None
    c = ctx.counters
    flops, nbytes = counts.risk_step_work(
        c["trials_per_step"], c["events_per_trial"], c["num_elts"],
        c["event_catalog"])
    least, _ = counts.least_time_s(flops, nbytes, ctx.peak)
    device_s = sum(e - s for s, e, _ in runs) / 1e9
    return 100.0 * len(runs) * least / device_s
