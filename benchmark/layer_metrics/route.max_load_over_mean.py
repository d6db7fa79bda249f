"""Expert layer: the fullest held expert's load over the mean load, from
the program's own routing (`parallel/dropless_moe.counters`): the largest
of the expert layers,
averaged over the samples of the run's reference check: each a sequence of
the cell's length routed alone, outside the timed step (the in-graph job
hands its readers nothing of the step itself: PERF.md, Open questions).
Source: program counter."""


def read(ctx):
    records = getattr(ctx.family, "routing_counters", None)
    if not records:
        return None
    return sum(max(r["max_load_over_mean"]) for r in records) / len(records)
