"""Kernels: the least time the chip could take for the gated short
convolution's calls of the window over the time they took, in percent.  A
call needs its BYTES: `[B | C | X]` read and y written forward, `[B | C |
X]` and dy read and their gradient written backward, each once, over the
chip's HBM bandwidth (`benchmark/reduce/conv_cost.py`; the same work
whatever implements it); the forward call made again under remat is a
call like any other, as in `flash_roofline`.  Source: device trace."""

from benchmark.reduce import conv_cost, flash_cost


def read(ctx):
    least = took = 0.0
    for name, start, end in ctx.ops(0):
        call = conv_cost.call(name)
        if call is None:
            continue
        least += flash_cost.least_seconds(*conv_cost.cost(*call),
                                          ctx.peaks)[0]
        took += (end - start) / 1e9
    return 100.0 * least / took if took else None
