"""PS wire: milliseconds one pull waited from its issue to its response
header, mean over the rounds' pulls: `recv_first_byte_us` over `pulls`.
What the server, and the wire's other direction, cost a pull before its
bytes flow.  Source: program counter."""

from benchmark.reduce import wire_counts


def read(ctx):
    wire = wire_counts.wire(ctx.dir)
    if wire is None or not wire.total["pulls"]:
        return None
    return wire.total["recv_first_byte_us"] / wire.total["pulls"] / 1e3
