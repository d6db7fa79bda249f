"""Kernels: milliseconds of a step inside the flash-attention calls under
a block-diffusion mask (`flash_fwd_bd4`, `flash_dq_bd4`, `flash_dkv_bd4`,
found by the name the kernel gives itself, `benchmark/reduce/bd_cost.py`),
forward, dQ and dK/dV, every layer's.  Nothing where no such call ran.
Source: device trace."""

from benchmark.reduce import bd_cost


def read(ctx):
    spans = [e - s for n, s, e in ctx.ops(0) if bd_cost.call(n)]
    if not spans:
        return None
    return sum(spans) / ctx.n_steps / 1e6
