"""Kernels: milliseconds of a step inside the delta-rule scan's calls on
chip 0, forward, recomputation under remat and backward alike (the Mosaic
calls the program names `kda_fwd_c<C>` / `kda_bwd_c<C>`,
`benchmark/reduce/kda_cost.py`).  Nothing where the program has no such
kernel.  Source: device trace."""

from benchmark.reduce import kda_cost


def read(ctx):
    spans = [e - s for n, s, e in ctx.ops(0) if kda_cost.call(n)]
    if not spans:
        return None
    return sum(spans) / ctx.n_steps / 1e6
