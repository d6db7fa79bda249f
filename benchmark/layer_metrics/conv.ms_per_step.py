"""Kernels: milliseconds of a step inside the gated short convolution's
kernels on chip 0 (`short_conv_fwd`, `short_conv_bwd`, found by the name
the kernel gives itself, `benchmark/reduce/conv_cost.py`): forward, the
forward call made again under remat, and backward, every convolution
layer's.  Nothing where no such call ran.  Source: device trace."""

from benchmark.reduce import conv_cost


def read(ctx):
    spans = [e - s for n, s, e in ctx.ops(0) if conv_cost.call(n)]
    if not spans:
        return None
    return sum(spans) / ctx.n_steps / 1e6
