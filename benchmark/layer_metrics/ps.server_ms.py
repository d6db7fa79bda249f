"""PS server: milliseconds of a step's critical partition inside the
server (engine queue + sum + wait for other workers), from the program's
merged comm.json.  Source: program span."""

from benchmark.reduce import comm_chain


def read(ctx):
    rows = comm_chain.rows(ctx.dir)
    return comm_chain.mean_us(rows, comm_chain.SERVER) / 1e3 if rows else None
