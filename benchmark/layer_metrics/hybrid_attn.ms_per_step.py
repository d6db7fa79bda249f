"""Kernels: milliseconds of a step inside the flash-attention kernels of
the hybrid model's attention layers on chip 0, forward, recomputation
under remat and the two backward kernels alike: the Mosaic calls that are
not the scan's (`benchmark/reduce/ssd_cost.py` `attention_call`).  Nothing
where no scan kernel ran: in another family's trace the flash calls are
`flash.*`'s or `attn.*`'s.  Source: device trace."""

from benchmark.reduce import ssd_cost


def read(ctx):
    if not any(ssd_cost.scan_call(n) for n, _, _ in ctx.ops(0)):
        return None
    spans = [e - s for n, s, e in ctx.ops(0) if ssd_cost.attention_call(n)]
    return sum(spans) / ctx.n_steps / 1e6 if spans else None
