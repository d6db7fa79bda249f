"""Kernels: the least time the chip could take for the two-width
flash-attention calls of the window over the time they took, in percent.
A call's FLOPs are those of the causal triangle, a pair's QK product 192
deep and its PV product 128 wide, times the products the kernel's
interface makes it form; its bytes what it must read and write, each
operand at its own width (`benchmark/reduce/mla_cost.py`).  Source: device
trace."""

from benchmark.reduce import flash_cost, mla_cost


def read(ctx):
    least = took = 0.0
    for name, start, end in ctx.ops(0):
        call = mla_cost.call(name)
        if call is None:
            continue
        least += flash_cost.least_seconds(*mla_cost.cost(*call),
                                          ctx.peaks)[0]
        took += (end - start) / 1e9
    return 100.0 * least / took if took else None
