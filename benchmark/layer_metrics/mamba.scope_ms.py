"""Model step: milliseconds of a step under the Mamba-2 mixer's scopes,
`granite.mamba.in_proj`, `.conv`, `.scan`, `.gate_norm`, `.out_proj`,
every pass: the input norm, both projections' every product, the
convolution, the scan's kernels and the transposes round them, the gated
norm.  `mamba.ms_per_step` reads the same layer from outside, by shapes,
and leaves out `out_proj`'s forward product, `in_proj`'s input gradient
and the transposes.  From the program's map of its step
(`benchmark/reduce/scopes.py`).  Source: program span."""

from benchmark.reduce import scopes


def read(ctx):
    return scopes.scope_ms(ctx, r"^granite\.mamba\.")
