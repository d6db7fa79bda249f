"""Model step: milliseconds of a step under the `qkv` child of
`joyai.attn`, every pass, the main stack's layers: the input norm, both
low-rank chains (one product down, a norm in the middle of each, one
product up each), the rotary turns and the rotary key laid beside every
head's own part.  `attn.around_kernel_ms` reads this and the output
projection together.  From the program's map of its step
(`benchmark/reduce/scopes.py`).  Source: program span."""

from benchmark.reduce import scopes


def read(ctx):
    return scopes.scope_ms(ctx, r"^joyai\.attn$", children=("qkv",))
