"""Share of the traced window in which the busiest device ran no
operation, in percent: 1 - (union of its operations' intervals) / window."""
from benchmarks.chip import trace


def read(ctx):
    busy = max(trace.busy_ns(ctx.events, d, ctx.lo, ctx.hi)
               for d in ctx.devices)
    return 100.0 * (1.0 - busy / (ctx.hi - ctx.lo))
