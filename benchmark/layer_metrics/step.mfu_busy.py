"""Model step: the model FLOPs of a step (the family's own count, no
recompute) over what the chip's bf16 peak would do in the time the step
keeps chip 0 busy, in percent.  Times the busy share of the window it is
the end-to-end MFU.  Source: device trace."""

from benchmark.reduce import intervals


def read(ctx):
    busy_s = intervals.total(ctx.busy(0)) / 1e9 if ctx.ops(0) else 0
    if not busy_s:
        return None
    flops = (ctx.family.model_flops_per_sample() * ctx.samples_per_step
             * ctx.n_steps / ctx.n_chips)
    return 100.0 * flops / (busy_s * ctx.peaks["bf16_flops_per_s"])
