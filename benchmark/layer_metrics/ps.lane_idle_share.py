"""PS wire: the share of `ps.wire_busy_ms`, in percent, that a lane had
no byte outstanding, mean over the lanes: 1 - `lane_busy_us` over the
rounds' wire-busy time.  Source: program counter over program span."""

from benchmark.reduce import wire_counts


def read(ctx):
    wire = wire_counts.wire(ctx.dir)
    if wire is None or not wire.lane_busy_us:
        return None
    busy = sum(wire.lane_busy_us) / len(wire.lane_busy_us)
    return 100.0 * (1.0 - busy / wire.wire_busy_us)
