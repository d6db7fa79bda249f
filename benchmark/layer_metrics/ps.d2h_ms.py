"""PS staging: milliseconds a round's calling thread spent in `D2H`
spans: the blocking copies of every unit off the device (with the wait
for the unit's pack program and the first touch of the new host memory).
Source: program span."""

from benchmark.reduce import program_spans


def read(ctx):
    rounds = program_spans.rounds(ctx.dir)
    return rounds and rounds.mean_ms("D2H")
