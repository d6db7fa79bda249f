"""PS wire: milliseconds a round's senders (the dispatcher with its
pushes, the receivers with their pull requests) waited for a lane's
send lock, summed over threads and lanes: `send_lock_wait_us` of the
`ROUND`s.  Source: program counter."""

from benchmark.reduce import wire_counts


def read(ctx):
    wire = wire_counts.wire(ctx.dir)
    return wire and wire.per_round_ms("send_lock_wait_us")
