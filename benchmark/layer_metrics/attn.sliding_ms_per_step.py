"""Kernels: milliseconds of a step inside the attention kernels of the
SLIDING layers, the flash calls that carry a window in their name
(`flash_fwd_w1024`, `benchmark/reduce/afmoe_cost.py` `attention_call`);
`attn.full_ms_per_step` is the rest of `attn.ms_per_step`.  Source:
device trace."""

from benchmark.reduce import afmoe_cost


def read(ctx):
    calls = [(afmoe_cost.attention_call(n), e - s) for n, s, e in ctx.ops(0)]
    spans = [t for call, t in calls if call and call[4] is not None]
    if not spans:
        return None
    return sum(spans) / ctx.n_steps / 1e6
