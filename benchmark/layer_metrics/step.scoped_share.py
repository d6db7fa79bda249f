"""Model step: of the time chip 0 is busy, the percentage in instructions
that the program's map of its step holds AND places under a scope of the
program's or in the optimizer: the health of the join itself
(`benchmark/reduce/scopes.py`).  A map of another executable than the one
that ran reads low here first.  The compiler's own kernels, which carry
no path, count at the scope the map lends them; how much of the share
that is stands in `scopes.json` beside the trace (`lent_share`).  Source:
program span."""

from benchmark.reduce import scopes


def read(ctx):
    return scopes.scoped_share(ctx)
