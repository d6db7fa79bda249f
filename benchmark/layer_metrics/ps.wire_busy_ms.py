"""PS wire: milliseconds of a round during which some partition's
`PUSH` or `PULL` span was open: the union over all partitions, where
`ps.wire_ms` follows one partition's chain.  The round's bytes over it
is the rate the loopback wire achieved.  Source: program span."""

from benchmark.reduce import program_spans


def read(ctx):
    rounds = program_spans.rounds(ctx.dir)
    return rounds and rounds.wire_busy_us / len(rounds.spans) / 1e3
