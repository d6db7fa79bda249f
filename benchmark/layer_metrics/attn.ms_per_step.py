"""Kernels: milliseconds of a step inside the attention kernels of the
afmoe model, sliding layers' and full layers' alike (the Mosaic custom
calls that `benchmark/reduce/afmoe_cost.py` knows for flash kernels; the
compiler's grouped products are Mosaic calls too and are not these).
Source: device trace."""

from benchmark.reduce import afmoe_cost


def read(ctx):
    spans = [e - s for n, s, e in ctx.ops(0) if afmoe_cost.attention_call(n)]
    if not spans:
        return None
    return sum(spans) / ctx.n_steps / 1e6
