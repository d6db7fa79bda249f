"""PS staging: milliseconds a round's calling thread spent in `PACK`
(dispatch of ravel / concatenate, compress) and `STAGE` spans (widening
to float32, result buffer, partitioning, inline encode, queue insert).
Source: program span."""

from benchmark.reduce import program_spans


def read(ctx):
    rounds = program_spans.rounds(ctx.dir)
    return rounds and rounds.mean_ms("PACK", "STAGE")
