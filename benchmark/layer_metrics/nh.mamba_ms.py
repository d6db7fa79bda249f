"""Model step: milliseconds of a step under the nemotron_h mixer's scopes,
`nemotronh.mamba.in_proj`, `.conv`, `.scan`, `.gate_norm`, `.out_proj`,
every pass, all the M layers of the step: the input norm, both
projections' every product, the convolution, the scan's kernels
(`ssd_fwd_c128` / `ssd_bwd_c128`, 8 groups) and the transposes round
them, the gated norm by groups.  With `nh.moe_ms`, `nh.attn_ms`, the
head, the embedding and the optimizer it accounts for the step.  From the
program's map of its step (`benchmark/reduce/scopes.py`).  Source: program
span."""

from benchmark.reduce import scopes


def read(ctx):
    return scopes.scope_ms(ctx, r"^nemotronh\.mamba\.")
