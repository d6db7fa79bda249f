"""Model step: milliseconds of a step under `sdar.noise`, every pass:
laying the mask token over the masked positions, the two copies side by
side, their positions, and the embedding's gather of the 2 L rows (and
its scatter-add in the backward pass).  From the program's map of its
step (`benchmark/reduce/scopes.py`).  Source: program span."""

from benchmark.reduce import scopes


def read(ctx):
    return scopes.scope_ms(ctx, r"^sdar\.noise$")
