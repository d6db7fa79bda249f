"""Model step: milliseconds of a step under the `kimi.kda.*` scopes other
than `kimi.kda.scan`, every pass: what a KDA mixer's projections, gates,
norms and convolution cost beside the scan's own products.  From the
program's map of its step (`benchmark/reduce/scopes.py`).  Source: program
span."""

from benchmark.reduce import scopes


def read(ctx):
    return scopes.scope_ms(ctx, r"^kimi\.kda\.(?!scan$)")
