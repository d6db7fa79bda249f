"""In-graph collectives: milliseconds of a step in which chip 0 has an
exchange with other chips under way (all-reduce and its kin, from start to
done where they are asynchronous).  Nothing on one chip, where
`build_train_step` traces them away.  Source: device trace."""

from benchmark.reduce import intervals


def read(ctx):
    spans = ctx.collectives(0)
    if not spans:
        return None
    return intervals.total(intervals.union(spans)) / ctx.n_steps / 1e6
