"""Share of the traced window (host clock) in which no operation ran on
the device: 1 - (union of device-operation intervals / window), averaged
over chips."""
from chipbench import trace_reduce


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices():
        return None
    busy_s = trace_reduce.busy_ns(ctx.trace, ctx.lo, ctx.hi) * 1e-9
    return 100.0 * (1.0 - busy_s / ctx.window.seconds)
