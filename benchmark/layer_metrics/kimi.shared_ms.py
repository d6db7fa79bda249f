"""Expert layer: milliseconds of a step under the `shared` child of
`kimi.moe`, every pass: the shared expert's three dense products (width
1024) on every token, which no routing thins out.  From the program's map
of its step (`benchmark/reduce/scopes.py`).  Source: program span."""

from benchmark.reduce import scopes


def read(ctx):
    return scopes.scope_ms(ctx, r"^kimi\.moe$", children=("shared",))
