"""PS wire: milliseconds a round's calling thread was blocked in
`PSHandle.wait` (`WAIT` spans): the wire and the server that nothing
hides.  Source: program span."""

from benchmark.reduce import program_spans


def read(ctx):
    rounds = program_spans.rounds(ctx.dir)
    return rounds and rounds.mean_ms("WAIT")
