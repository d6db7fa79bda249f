"""Kernels: the least time the chip could take for the hybrid model's
attention calls of the window over the time they took, in percent.  A
call's FLOPs are those of the causal triangle and its bytes each operand
once (`benchmark/reduce/flash_cost.py`; the key-value heads are repeated
before the kernel, so a call reads as many as it has query heads); the
recomputation under remat is a call like any other, as in
`flash_roofline`.  Nothing where no scan kernel ran.  Source: device
trace."""

from benchmark.reduce import flash_cost, ssd_cost


def read(ctx):
    if not any(ssd_cost.scan_call(n) for n, _, _ in ctx.ops(0)):
        return None
    least = took = 0.0
    for name, start, end in ctx.ops(0):
        call = ssd_cost.attention_call(name)
        if call is None:
            continue
        flops, nbytes = flash_cost.cost(*call, True)
        least += flash_cost.least_seconds(flops, nbytes, ctx.peaks)[0]
        took += (end - start) / 1e9
    return 100.0 * least / took if took else None
