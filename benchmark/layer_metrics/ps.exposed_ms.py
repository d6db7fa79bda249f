"""Entry / trainer: milliseconds a step leaves chip 0 idle while the
calling thread is inside a round: chip 0's idle intervals cut with the
program's `byteps.round` annotations, both on the profiler's clock.
The part of `ps.entry.host_gap_ms` that the PS round answers for, and
what must fall for `ps_tokens_per_s` to rise.  Source: program span."""

from benchmark.reduce import intervals, program_spans


def read(ctx):
    if not ctx.ops(0):
        return None
    idle = intervals.gaps(ctx.busy(0), *ctx.window)
    exposed = program_spans.exposed_ns(ctx.dir, idle)
    return None if exposed is None else exposed / ctx.n_steps / 1e6
