"""Kernels: the least time the chip could take for the flash-attention
calls of the window (FLOPs and bytes from their shapes, by
`benchmark/reduce/flash_cost.py`; at S = 1024 and head size 64 all three
kernels are bound by compute) over the time they took, in percent.
Source: device trace."""

from benchmark.reduce import flash_cost


def read(ctx):
    least = took = 0.0
    for name, start, end in ctx.ops(0):
        call = flash_cost.classify(name) if flash_cost.is_kernel(name) \
            else None
        if call is None:
            continue
        flops, nbytes = flash_cost.cost(*call, ctx.family.causal_attention)
        least += flash_cost.least_seconds(flops, nbytes, ctx.peaks)[0]
        took += (end - start) / 1e9
    return 100.0 * least / took if took else None
