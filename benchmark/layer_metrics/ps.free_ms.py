"""PS staging: milliseconds a round's calling thread spent in its `FREE`
span: giving back the round's host memory (the copies off the device and
the result buffers, twice the tree's bytes) before `push_pull_tree`
returns.  A program that kept those buffers from round to round would
not pay it.  Source: program span."""

from benchmark.reduce import program_spans


def read(ctx):
    rounds = program_spans.rounds(ctx.dir)
    return rounds and rounds.mean_ms("FREE")
