"""Model step: milliseconds of a step under the `post_norm` children of a
looped model's attention and feed-forward halves (`ouro.attn.*/post_norm`,
`ouro.mlp/post_norm`), every pass and every layer application: the two
RMS norms of a sub-layer's OUTPUT that the sandwich adds to a layer, with
the residual's add.  From the program's map of its step
(`benchmark/reduce/scopes.py`).  Source: program span."""

from benchmark.reduce import scopes


def read(ctx):
    return scopes.scope_ms(ctx, r"^ouro\.(attn|mlp)", children=("post_norm",))
