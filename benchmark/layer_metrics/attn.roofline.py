"""Kernels: the least time the chip could take for the attention calls of
the window over the time they took, in percent.  A call's FLOPs are those
of the (query, key) pairs ITS layer kind needs, the causal triangle in a
full layer and the window's band in a sliding one (told apart by the
kernel's name, `benchmark/reduce/afmoe_cost.py`); the recompute under
remat is a call like any other, as in `flash_roofline`.  Source: device
trace."""

from benchmark.reduce import afmoe_cost, flash_cost


def read(ctx):
    least = took = 0.0
    for name, start, end in ctx.ops(0):
        call = afmoe_cost.attention_call(name)
        if call is None:
            continue
        flops, nbytes = afmoe_cost.attention_cost(*call)
        least += flash_cost.least_seconds(flops, nbytes, ctx.peaks)[0]
        took += (end - start) / 1e9
    return 100.0 * least / took if took else None
