"""PS wire: socket calls the worker made for a megabyte (10^6 bytes) a
round moved: `send_calls + recv_calls` of the `ROUND`s over their
`bytes_out + bytes_in`.  Source: program counter."""

from benchmark.reduce import wire_counts


def read(ctx):
    wire = wire_counts.wire(ctx.dir)
    if wire is None:
        return None
    calls = wire.total["send_calls"] + wire.total["recv_calls"]
    return calls / (wire.bytes / 1e6)
