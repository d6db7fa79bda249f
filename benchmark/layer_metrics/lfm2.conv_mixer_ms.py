"""Model step: milliseconds of a step under the `lfm2.conv.*` scopes,
every pass: a convolution mixer's input norm and `in_proj`, the gated
convolution between them and `out_proj`, and the gradients of all three,
every convolution layer's.  From the program's map of its step
(`benchmark/reduce/scopes.py`).  Source: program span."""

from benchmark.reduce import scopes


def read(ctx):
    return scopes.scope_ms(ctx, r"^lfm2\.conv\.")
