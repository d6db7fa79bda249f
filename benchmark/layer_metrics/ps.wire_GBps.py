"""PS wire: gigabytes a second the traced rounds moved, both ways, while
some partition was on the wire: their `ROUND`s' `bytes_out + bytes_in`
over `ps.wire_busy_ms`.  Read beside `ps.wire_floor_GBps`; a program
whose `ROUND` lacks the wire's counts reads nothing
(reduce/wire_counts.py).  Source: program span."""

from benchmark.reduce import wire_counts


def read(ctx):
    wire = wire_counts.wire(ctx.dir)
    return wire and wire.GB_per_s
