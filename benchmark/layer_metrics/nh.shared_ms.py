"""Expert layer: milliseconds of a step under the `shared` child of
`nemotronh.moe`, every pass: the shared expert's two dense products (width
3712) and the squared ReLU between them, on every token, which no routing
thins out.  From the program's map of its step
(`benchmark/reduce/scopes.py`).  Source: program span."""

from benchmark.reduce import scopes


def read(ctx):
    return scopes.scope_ms(ctx, r"^nemotronh\.moe$", children=("shared",))
