"""Entry / trainer: milliseconds a step leaves chip 0 with no instruction
running, which is the time the device waits for the host.  Source: device
trace.  The result line's `breakdown.idle_gaps` says which annotation of
the benchmark the host was in."""

from benchmark.reduce import intervals


def read(ctx):
    if not ctx.ops(0):
        return None
    idle = intervals.gaps(ctx.busy(0), *ctx.window)
    return intervals.total(idle) / ctx.n_steps / 1e6
