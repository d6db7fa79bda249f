"""Expert layer: the pairs past the static buffer, which the exact path
took, from the program's own routing (`parallel/dropless_moe.counters`):
summed over the expert layers,
averaged over the samples of the run's reference check: each a sequence of
the cell's length routed alone, outside the timed step (the in-graph job
hands its readers nothing of the step itself: PERF.md, Open questions).
Source: program counter."""


def read(ctx):
    records = getattr(ctx.family, "routing_counters", None)
    if not records:
        return None
    return sum(sum(r["overflow_rows"]) for r in records) / len(records)
