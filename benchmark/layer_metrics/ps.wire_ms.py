"""PS wire: milliseconds of a step's critical partition on the worker's
side of the wire (queue + encode + push + pull + decode, less the
server's part), from the program's merged comm.json.  With hundreds of
partitions in flight most of it is time queued behind others, not time on
the socket.  Source: program span."""

from benchmark.reduce import comm_chain


def read(ctx):
    rows = comm_chain.rows(ctx.dir)
    return comm_chain.mean_us(rows, comm_chain.WIRE) / 1e3 if rows else None
