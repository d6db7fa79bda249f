"""What a flash-attention call with TWO widths has to compute and move
(latent attention: queries and keys `dk` wide, values `dv`), from its
name and its shapes, beside `flash_cost.py`, which counts the calls of one
width.

`ops/flash_attention.py` names such a call after its kind and both
widths: `flash_fwd_d192x128`, `flash_dq_d192x128`, `flash_dkv_d192x128`
(a one-width call carries no `_d`).  The instruction's text gives the
rest: of its 3-D arrays, [BH, S, .] with S over 1, BH and S.

A call needs the (query, key) pairs of the causal triangle, S (S + 1) / 2
a head, and of each pair the products its interface makes it form, 2
FLOPs a multiply-add:

    forward   S = Q K^T (dk deep), O = P V (dv wide)        dk + dv
    dq        S, dP = dO V^T (dv), dQ = dS K (dk)           2 dk + dv
    dkv       S, dP, dV = P^T dO (dv), dK = dS^T Q (dk)     2 dk + 2 dv

(the backward pass is two kernels, each of which forms S and dP again:
counted as needed by the call, as `flash_cost.py` does).  Bytes are each
operand read once and each result written once, whatever implements it:

    forward   q, k (dk); v, o (dv); lse
    dq        q, k, dq (dk); v, do (dv); lse, delta
    dkv       q, k, dk (dk); v, do, dv (dv); lse, delta

A key's rotary part repeated over the heads is counted as the call reads
it, a key a head.
"""

from __future__ import annotations

import re

from benchmark.reduce import flash_cost, xplane

_NAMED = re.compile(r"^flash_(fwd|dq|dkv)_d(\d+)x(\d+)")
_ARRAY = re.compile(r"(?:bf16|f32|f16)\[([\d,]+)\]")
# kind: (dk-deep products, dv-deep products, [BH,S,dk] arrays, [BH,S,dv]
# arrays, [BH,S] float32 rows) a call forms, reads or writes
_KINDS = {"fwd": (1, 1, 2, 2, 1), "dq": (2, 1, 3, 2, 2),
          "dkv": (2, 2, 3, 3, 2)}


def call(instruction: str):
    """`(kind, BH, S, dk, dv)` of a two-width flash kernel's instruction,
    or None for anything else."""
    m = _NAMED.match(xplane.op_name(instruction))
    if m is None or not flash_cost.is_kernel(instruction):
        return None
    for dims in _ARRAY.findall(instruction):
        shape = tuple(map(int, dims.split(",")))
        if len(shape) == 3 and shape[1] != 1:
            return m.group(1), shape[0], shape[1], int(m.group(2)), int(
                m.group(3))
    return None


def cost(kind: str, bh: int, s: int, dk: int, dv: int, itemsize: int = 2):
    """`(flops, bytes)` one causal call needs."""
    deep_k, deep_v, wide_k, wide_v, rows = _KINDS[kind]
    pairs = s * (s + 1) / 2
    flops = 2.0 * bh * pairs * (deep_k * dk + deep_v * dv)
    nbytes = bh * s * ((wide_k * dk + wide_v * dv) * itemsize + rows * 4)
    return flops, float(nbytes)
