"""Kernels: milliseconds of a step inside the flash-attention calls whose
queries and keys are one width and values another (latent attention:
`flash_fwd_d192x128`, `flash_dq_d192x128`, `flash_dkv_d192x128`, found by
the name the kernel gives itself, `benchmark/reduce/mla_cost.py`), every
layer's and the prediction module's.  Nothing where no such call ran.
Source: device trace."""

from benchmark.reduce import mla_cost


def read(ctx):
    spans = [e - s for n, s, e in ctx.ops(0) if mla_cost.call(n)]
    if not spans:
        return None
    return sum(spans) / ctx.n_steps / 1e6
