"""Model step: milliseconds a step keeps chip 0 busy, the union of the
intervals in which an instruction ran.  Source: device trace."""

from benchmark.reduce import intervals


def read(ctx):
    if not ctx.ops(0):
        return None
    return intervals.total(ctx.busy(0)) / ctx.n_steps / 1e6
