"""PS staging: the round less the critical partition's chain, which is
what has no span today: device to host, widening to float32, fusion
packing, host to device.  Source: program span."""

from benchmark.harness.readers import reader
from benchmark.reduce import comm_chain


def read(ctx):
    round_ms = reader("ps.round_ms")(ctx)
    rows = comm_chain.rows(ctx.dir)
    if round_ms is None or not rows:
        return None
    chain = comm_chain.mean_us(rows, comm_chain.WIRE + comm_chain.SERVER)
    return round_ms - chain / 1e3
