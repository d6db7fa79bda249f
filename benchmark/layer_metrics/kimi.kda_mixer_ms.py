"""Model step: milliseconds of a step under the `kimi.kda.*` scopes, every
pass: a KDA mixer's input norm and projection, the convolution, the gates,
the scan, the gated head norm and the output projection, and the
gradients of all of them, every KDA layer's.  From the program's map of
its step (`benchmark/reduce/scopes.py`).  Source: program span."""

from benchmark.reduce import scopes


def read(ctx):
    return scopes.scope_ms(ctx, r"^kimi\.kda\.")
