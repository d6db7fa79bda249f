"""What a flash-attention call under a BLOCK-DIFFUSION mask has to compute
and move, from its name and its shapes, beside `flash_cost.py`, which
counts the calls of the other masks.

`ops/flash_attention.py` names such a call after its kind and the mask's
block length: `flash_fwd_bd4`, `flash_dq_bd4`, `flash_dkv_bd4`.  The
instruction's text gives the rest: of its 3-D arrays, [BH, S, D] with S
over 1, the heads, the rows S = 2 L (the clean copy and the noised one of
L tokens) and the head size.

The yardstick is the MASK, not the walk that implements it: a clean row
sees the clean keys of its own and earlier blocks, a noised row the clean
keys of earlier blocks and the noised keys of its own, which is

    L^2 / 2 + L beta / 2  +  L^2 / 2 - L beta / 2  +  L beta  =  L^2 + L beta

(row, key) pairs a head, whatever tiles a kernel visits to compute them:
a later kernel that visits fewer tiles cannot move its own yardstick.  Of
each pair the products the call's interface makes it form, 2 FLOPs a
multiply-add (`flash_cost.py`: forward 2 matmuls, dq 3, dkv 4, the two
backward kernels each forming S and dP again); bytes are each operand
read once and each result written once.
"""

from __future__ import annotations

import re

from benchmark.reduce import flash_cost, xplane

_NAMED = re.compile(r"^flash_(fwd|dq|dkv)_bd(\d+)")
_ARRAY = re.compile(r"(?:bf16|f32|f16)\[([\d,]+)\]")
_KINDS = {"fwd": "forward", "dq": "dq", "dkv": "dkv"}


def needed_pairs(L: int, beta: int) -> int:
    """(row, key) pairs a head under the mask over two copies of L
    tokens in blocks of `beta`."""
    return L * (L + beta)


def call(instruction: str):
    """`(kind, BH, L, D, beta)` of a block-diffusion flash kernel's
    instruction, `kind` as `flash_cost` spells it, or None for anything
    else."""
    m = _NAMED.match(xplane.op_name(instruction))
    if m is None or not flash_cost.is_kernel(instruction):
        return None
    for dims in _ARRAY.findall(instruction):
        shape = tuple(map(int, dims.split(",")))
        if len(shape) == 3 and shape[1] != 1:
            return (_KINDS[m.group(1)], shape[0], shape[1] // 2, shape[2],
                    int(m.group(2)))
    return None


def cost(kind: str, bh: int, L: int, d: int, beta: int, itemsize: int = 2):
    """`(flops, bytes)` one call needs, from `(L, beta, heads, head
    size)` alone."""
    matmuls, wide, rows = flash_cost._KINDS[kind]
    flops = matmuls * 2.0 * bh * needed_pairs(L, beta) * d
    return flops, float(wide * bh * 2 * L * d * itemsize
                        + rows * bh * 2 * L * 4)
