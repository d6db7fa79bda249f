"""In-graph collectives: the part of `coll.ms_per_step` during which no
other instruction runs on chip 0, which is what the exchange adds to the
step.  Source: device trace."""

from benchmark.reduce import intervals


def read(ctx):
    spans = ctx.collectives(0)
    if not spans:
        return None
    return intervals.exposed(spans, ctx.compute(0)) / ctx.n_steps / 1e6
