"""Model step: milliseconds of a step under `joyai.dense`, every pass:
the leading dense layer's norm and its SwiGLU of width 7168 on every
token.  From the program's map of its step
(`benchmark/reduce/scopes.py`).  Source: program span."""

from benchmark.reduce import scopes


def read(ctx):
    return scopes.scope_ms(ctx, r"^joyai\.dense$")
