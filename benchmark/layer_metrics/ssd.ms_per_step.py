"""State-space scan: milliseconds of a step inside the scan's kernels on
chip 0, forward, recomputation under remat and backward alike (the Mosaic
calls the program names `ssd_fwd_c<Q>` / `ssd_bwd_c<Q>`,
`benchmark/reduce/ssd_cost.py`).  Nothing where the program has no such
kernel.  Source: device trace."""

from benchmark.reduce import ssd_cost


def read(ctx):
    spans = [e - s for n, s, e in ctx.ops(0) if ssd_cost.scan_call(n)]
    if not spans:
        return None
    return sum(spans) / ctx.n_steps / 1e6
