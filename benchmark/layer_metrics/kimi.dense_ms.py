"""Model step: milliseconds of a step under `kimi.dense`, every pass: the
leading dense layer's norm and its SwiGLU of width 9216 on every token.
From the program's map of its step (`benchmark/reduce/scopes.py`).
Source: program span."""

from benchmark.reduce import scopes


def read(ctx):
    return scopes.scope_ms(ctx, r"^kimi\.dense$")
