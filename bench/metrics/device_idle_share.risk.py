"""``device_idle_share.risk``: the share of the traced window in which no
operation ran on a chip, averaged over the cell's chips, in %."""
from bench import traces


def read(ctx):
    if not ctx.trace.ops:
        return None
    return 100.0 * traces.idle_share(ctx.trace)
