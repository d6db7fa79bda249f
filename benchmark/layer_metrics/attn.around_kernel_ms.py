"""Model step: milliseconds of a step under the `qkv` and `out` children
of every attention half (`<family>.attn*`): the input norm, the
projections, the q / k norms, the rotary passes, K and V repeated for
the query heads, the gate, the output projection, the norm after.  The
kernels and the transposes beside them are the half's own time and have
their metrics (`attn.ms_per_step`, `hybrid_attn.ms_per_step`).  From the
program's map of its step (`benchmark/reduce/scopes.py`).  Source: program
span."""

from benchmark.reduce import scopes


def read(ctx):
    return scopes.scope_ms(ctx, r"\.attn", children=("qkv", "out"))
