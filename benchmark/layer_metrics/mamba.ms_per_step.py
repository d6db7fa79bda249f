"""Mamba-2 mixer: milliseconds of a step in the instructions of the mamba
layers' mixers that can be told from the trace: the scan's kernels, and
what touches the step's tokens and returns a dimension only the mixer has:
`in_proj`'s width, the convolution's channels, the inner width
(`benchmark/reduce/ssd_cost.py` `is_mixer` says what is found so and what
is not: the optimizer's update of the mixer's weights and the casts of
their stacks are not the mixer's).  Nothing where no scan kernel ran:
shapes alone prove nothing.  Source: device trace."""

from benchmark.reduce import ssd_cost, xplane


def read(ctx):
    if not any(ssd_cost.scan_call(n) for n, _, _ in ctx.ops(0)):
        return None
    cfg = ctx.family.cfg
    marks = {cfg.d_inner + cfg.conv_dim + cfg.mamba_n_heads, cfg.conv_dim,
             cfg.d_inner}
    positions = ctx.family.seq_len
    tokens = {positions, positions * ctx.samples_per_step // ctx.n_chips}
    spans = [e - s for n, s, e in xplane.leaves(ctx.ops(0))
             if ssd_cost.is_mixer(n, marks, tokens)]
    return sum(spans) / ctx.n_steps / 1e6 if spans else None
