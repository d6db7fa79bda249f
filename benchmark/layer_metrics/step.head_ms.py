"""Model step: milliseconds of a step under the family's `<family>.head`
scope, every pass: the last norm, the streamed head's chunks (forward,
and again under their own checkpoint) and the cross-entropy.  From the
program's map of its step (`benchmark/reduce/scopes.py`).  Source: program
span."""

from benchmark.reduce import scopes


def read(ctx):
    return scopes.scope_ms(ctx, r"\.head$")
