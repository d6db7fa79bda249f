"""Expert layer: milliseconds of a step under the family's `<family>.moe`
scope, every pass and every child: the norm before the router, routing
and sort, gather, the grouped products, scatter, the exact path behind
the buffer, afmoe's shared expert (down-projection too), the norm and
the add after.  `moe.ms_per_step` reads the same layer from outside, by
shapes, and leaves out what no shape tells.  From the program's map of
its step (`benchmark/reduce/scopes.py`).  Source: program span."""

from benchmark.reduce import scopes


def read(ctx):
    return scopes.scope_ms(ctx, r"\.moe$")
