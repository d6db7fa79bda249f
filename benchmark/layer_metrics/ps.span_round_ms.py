"""PS staging: milliseconds of one `bps.push_pull_tree` call by the
program's own `ROUND` span, entry to return, mean over the traced
rounds.  Unlike `ps.round_ms` it does not wait for the pulled tree to be
ready, and it stays a round's time once a program overlaps the round
with the backward pass.  Source: program span."""

from benchmark.reduce import program_spans


def read(ctx):
    rounds = program_spans.rounds(ctx.dir)
    if rounds is None:
        return None
    return sum(e - s for s, e in rounds.spans) / len(rounds.spans) / 1e3
