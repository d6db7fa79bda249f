"""Kernels: the least time the chip could take for the flash-attention
calls under a block-diffusion mask of the window over the time they took,
in percent.  A call's FLOPs are those of the L^2 + L beta pairs a head the
MASK needs, whatever tiles the kernel walked for them, times the products
the kernel's interface makes it form; its bytes what it must read and
write (`benchmark/reduce/bd_cost.py`).  Source: device trace."""

from benchmark.reduce import bd_cost, flash_cost


def read(ctx):
    least = took = 0.0
    for name, start, end in ctx.ops(0):
        call = bd_cost.call(name)
        if call is None:
            continue
        least += flash_cost.least_seconds(*bd_cost.cost(*call),
                                          ctx.peaks)[0]
        took += (end - start) / 1e9
    return 100.0 * least / took if took else None
