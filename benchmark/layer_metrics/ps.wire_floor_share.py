"""PS wire: `ps.wire_GBps` as a share of `ps.wire_floor_GBps`, in
percent: how much of what this host's sockets give the rounds' wire
took.  A reading over 100 says the probe is not the floor.  Source:
program span over the program's own probe."""

from benchmark.reduce import wire_counts


def read(ctx):
    wire, floor = wire_counts.wire(ctx.dir), wire_counts.floor(ctx.dir)
    if wire is None or floor is None:
        return None
    return 100.0 * wire.GB_per_s / floor["duplex"]["GB_per_s"]
