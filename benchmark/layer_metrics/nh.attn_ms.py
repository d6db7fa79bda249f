"""Model step: milliseconds of a step under `nemotronh.attn`, every pass
and every child, all the * layers of the step: the input norm, the
projections, K and V repeated for the 16 query heads of a key-value head,
the flash kernels (streaming at 16,384 positions) and the transposes
beside them, the output projection.  From the program's map of its step
(`benchmark/reduce/scopes.py`).  Source: program span."""

from benchmark.reduce import scopes


def read(ctx):
    return scopes.scope_ms(ctx, r"^nemotronh\.attn$")
