"""PS wire: milliseconds a round's receiver threads spent inside the
socket calls that receive PAYLOADS, summed over the lanes: `recv_us` of
the `ROUND`s (the wait for a response header is `ps.pull_first_byte_ms`,
not this).  Source: program counter."""

from benchmark.reduce import wire_counts


def read(ctx):
    wire = wire_counts.wire(ctx.dir)
    return wire and wire.per_round_ms("recv_us")
