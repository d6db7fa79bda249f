"""Kernels: milliseconds of a step inside the flash-attention kernels
(Mosaic custom calls) on chip 0.  Source: device trace."""

from benchmark.reduce import flash_cost


def read(ctx):
    spans = [(e - s) for n, s, e in ctx.ops(0) if flash_cost.is_kernel(n)]
    if not spans:
        return None
    return sum(spans) / ctx.n_steps / 1e6
