"""What a call of one of the program's flash-attention kernels has to
compute and move, from its shapes, and the least time a chip could take.

The kernels are Mosaic custom calls (`custom_call_target=
"tpu_custom_call"` in the instruction's text; `ops/flash_attention.py`
gives its `pallas_call`s no name, so the instructions are called after
the jaxpr around them: `closed_call`, `rematted_computation`,
`checkpoint`).  Three kinds, told apart by what they return:

    forward   (o bf16[BH,S,D], lse f32[BH,1,S])   S = QK^T, O = PV
    dq        dq bf16[BH,S,D]                     S, dP = dO V^T, dQ = dS K
    dkv       (dk, dv), both bf16[BH,S,D]         S, dP, dV = P^T dO,
                                                  dK = dS^T Q

Each matmul is 2 * BH * S * S * D FLOPs over the full square; under a
causal mask half of the square is needed.  The backward pass is two
kernels here, each of which has to form S and dP again: that is counted
as needed by the call, being what a kernel with that interface must do
(a fused backward would need 5 matmuls where these two need 7).
Softmax's exponentials and the rescaling are not counted.  Bytes are each
operand read once and each result written once.
"""

from __future__ import annotations

import re

TARGET = 'custom_call_target="tpu_custom_call"'
_ARRAY = re.compile(r"(bf16|f32|f16)\[([\d,]+)\]")
# kind: (matmuls, [BH,S,D] arrays read or written, [BH,S] float32 rows)
_KINDS = {"forward": (2, 4, 1), "dq": (3, 5, 2), "dkv": (4, 6, 2)}


def is_kernel(instruction: str) -> bool:
    return TARGET in instruction


def classify(instruction: str):
    """`(kind, BH, S, D)` of a flash kernel's instruction text, or None
    for a custom call that is not one of the three."""
    result, _, rest = instruction.split(" = ", 1)[-1].partition(
        " custom-call(")
    outs = [(t, tuple(map(int, dims.split(","))))
            for t, dims in _ARRAY.findall(result)]
    wide = [shape for t, shape in outs if len(shape) == 3 and shape[1] != 1]
    if not rest or not wide or any(s != wide[0] for s in wide):
        return None
    if len(outs) == 1:
        kind = "dq"
    elif len(outs) == 2 and len(wide) == 2:
        kind = "dkv"
    elif len(outs) == 2 and outs[1][0] == "f32":
        kind = "forward"
    else:
        return None
    return (kind, *wide[0])


def cost(kind: str, bh: int, s: int, d: int, causal: bool,
         itemsize: int = 2):
    """`(flops, bytes)` one call needs."""
    matmuls, wide, rows = _KINDS[kind]
    flops = matmuls * 2.0 * bh * s * s * d * (0.5 if causal else 1.0)
    return flops, float(wide * bh * s * d * itemsize + rows * bh * s * 4)


def least_seconds(flops: float, nbytes: float, peaks: dict):
    """The roofline: `(seconds, which bound)`."""
    compute = flops / peaks["bf16_flops_per_s"]
    memory = nbytes / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
