"""Expert layer: the pairs routed to held experts over the tokens, from
the program's own routing (`parallel/dropless_moe.counters`): the mean
over the expert layers (1.0 under even routing),
averaged over the samples of the run's reference check: each a sequence of
the cell's length routed alone, outside the timed step (the in-graph job
hands its readers nothing of the step itself: PERF.md, Open questions).
Source: program counter."""


def read(ctx):
    records = getattr(ctx.family, "routing_counters", None)
    if not records:
        return None
    means = [sum(r["held_rows_per_token"]) / len(r["held_rows_per_token"])
             for r in records]
    return sum(means) / len(means)
