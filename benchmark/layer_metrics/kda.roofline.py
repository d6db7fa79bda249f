"""Kernels: the least time the chip could take for the delta-rule scan's
calls of the window over the time they took, in percent.  A call's FLOPs
and bytes are what the chunked form needs at the call's chunk for the
family's shapes (`benchmark/reduce/kda_cost.py`, which says what is and
is not counted), the larger of the two over the chip's peaks; the
recomputation under remat is a call like any other, as in
`flash_roofline`.  Source: device trace."""

from benchmark.reduce import flash_cost, kda_cost


def read(ctx):
    shape = getattr(ctx.family, "kda_shape", None)
    if shape is None:
        return None
    shape = shape()
    sequences = ctx.samples_per_step // ctx.n_chips
    least = took = 0.0
    for name, start, end in ctx.ops(0):
        call = kda_cost.call(name)
        if call is None:
            continue
        kind, chunk = call
        flops, nbytes = kda_cost.cost(kind, **shape, chunk=chunk)
        least += sequences * flash_cost.least_seconds(
            flops, nbytes, ctx.peaks)[0]
        took += (end - start) / 1e9
    return 100.0 * least / took if took else None
