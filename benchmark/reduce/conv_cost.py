"""What a call of the gated short convolution has to move, from its name
and its shapes, beside `flash_cost.py`.

`ops/short_conv.py` names its two calls `short_conv_fwd` and
`short_conv_bwd`; the instruction's text gives the rest: the forward call
returns y [T, C], the backward call (d bcx [T, 3 C], dw [8, C]), T the
batch's sequences end to end.

    y_t = C_t * sum_k w_k (B * X)_{t-(K-1)+k}             [B | C | X] = bcx

The operator is elementwise but for K - 1 neighbours, so what a call
NEEDS is bytes, each operand read once and each result written once,
whatever implements it:

    forward    bcx [T, 3 C] read, y [T, C] written                 4 T C
    backward   bcx [T, 3 C] and dy [T, C] read, d bcx written      7 T C

elements of the activations' size (the taps and their gradient are K C
numbers and are not counted).  Its arithmetic, a few multiply-adds an
element on the vector unit, is counted too, for `least_seconds` to say
that the bytes bind: 2 K + 1 FLOPs an element forward (a product, K
multiply-adds, a product), 8 K + 5 backward (z again, dz, du's K
multiply-adds, dw's K, three products).
"""

from __future__ import annotations

import re

from benchmark.reduce import flash_cost, xplane

_NAMED = re.compile(r"^short_conv_(fwd|bwd)")
_ARRAY = re.compile(r"(?:bf16|f32|f16)\[([\d,]+)\]")
TAPS = 3


def call(instruction: str):
    """`(kind, T, C)` of a gated convolution kernel's instruction, or None
    for anything else."""
    m = _NAMED.match(xplane.op_name(instruction))
    if m is None or not flash_cost.is_kernel(instruction):
        return None
    result = instruction.split(" = ", 1)[-1].partition(" custom-call(")[0]
    dims = _ARRAY.search(result)
    if dims is None:
        return None
    rows, wide = map(int, dims.group(1).split(","))
    return m.group(1), rows, wide if m.group(1) == "fwd" else wide // 3


def cost(kind: str, rows: int, width: int, itemsize: int = 2,
         taps: int = TAPS):
    """`(flops, bytes)` one call needs."""
    wide, per = {"fwd": (4, 2 * taps + 1), "bwd": (7, 8 * taps + 5)}[kind]
    return float(per * rows * width), float(wide * rows * width * itemsize)
