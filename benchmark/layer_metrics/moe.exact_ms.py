"""Expert layer: milliseconds of a step under the `exact` child of the
family's `<family>.moe` scope: everything of the path behind the buffer
(`dropless_moe._past_the_buffer`), forward and hand-written backward,
its loops' conditions and its zeroed accumulators included, whether a
pair went that way or not.  From the program's map of its step
(`benchmark/reduce/scopes.py`).  Source: program span."""

from benchmark.reduce import scopes


def read(ctx):
    return scopes.scope_ms(ctx, r"\.moe$", children=("exact",))
