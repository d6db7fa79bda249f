"""Expert layer: milliseconds of a step under the `gather` and `scatter`
children of the family's `<family>.moe` scope: the first buffer's rows
fetched by token and masked, and its results weighted and added back,
both passes; what a gather kernel would take on.  The exact path's own
gather and scatter are in `moe.exact_ms`.  From the program's map of its
step (`benchmark/reduce/scopes.py`).  Source: program span."""

from benchmark.reduce import scopes


def read(ctx):
    return scopes.scope_ms(ctx, r"\.moe$", children=("gather", "scatter"))
