"""Model step: milliseconds of a step under the Mamba-2 mixer's
convolution scope, `<family>.mamba.conv` (`granite.mamba.conv`,
`nemotronh.mamba.conv`), every pass and every mixer of the step: the
4-tap depthwise convolution with its bias and silu, forward, again under
remat and backward, the copy that hands it `xBC` out of `in_proj`'s
result, and the split into x, B and C after it.  Inside
`mamba.scope_ms` / `nh.mamba_ms`.  None for a cell without the scope.
From the program's map of its step (`benchmark/reduce/scopes.py`).
Source: program span."""

from benchmark.reduce import scopes


def read(ctx):
    return scopes.scope_ms(ctx, r"\.mamba\.conv$")
