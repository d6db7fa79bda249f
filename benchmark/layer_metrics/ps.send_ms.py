"""PS wire: milliseconds a round's senders spent inside sending socket
calls, summed over threads and lanes: `send_us` of the `ROUND`s.  Near
`ps.wire_busy_ms` where one thread's `sendmsg` is what the wire waits
for; near that over the lanes where every lane sends at once.  Source:
program counter."""

from benchmark.reduce import wire_counts


def read(ctx):
    wire = wire_counts.wire(ctx.dir)
    return wire and wire.per_round_ms("send_us")
