"""Model step: milliseconds of a step under `nemotronh.moe`, every pass
and every child, all the E layers of the step: the norm before the
router, routing and sort, gather, the two grouped products of the held
experts at width 1856, scatter, the exact path behind the buffer, and the
shared expert.  `moe.scope_ms` reads the same scope for any family; this
is the step's account by KIND of layer, beside `nh.mamba_ms` and
`nh.attn_ms`.  From the program's map of its step
(`benchmark/reduce/scopes.py`).  Source: program span."""

from benchmark.reduce import scopes


def read(ctx):
    return scopes.scope_ms(ctx, r"^nemotronh\.moe$")
