"""What the afmoe step's new parts have to compute and move, from the
text of their instructions in a device trace, and which instructions they
are.  The trace carries no source operation (`xplane.py`), so each part
is found by what its instruction is called or returns:

attention
    The flash kernels are Mosaic custom calls.  A sliding layer's are
    named by the kernel itself, `flash_fwd_w2048`, `flash_dq_w2048`,
    `flash_dkv_w2048` (`ops/flash_attention.py` names a windowed call
    after its kind and its window).  A full layer's have no name of their
    own and are called after the scope around them
    (`afmoe.attn.full_attention`, `rematted_computation`, ...): they are
    the other Mosaic calls that `flash_cost.classify` knows by their
    results.  A call needs the (query, key) pairs its mask leaves: the
    causal triangle, or the window's band.

the grouped products
    `lax.ragged_dot` is, on a TPU, the compiler's own kernel: one
    instruction `ragged-dot-none*` a product (forward, gradient of the
    rows, gradient of the weights alike) and a small `ragged-dot-metadata*`
    before it.  The instruction's text gives the shapes of result and
    operands; of the three, one is [experts, A, B] and the others
    [rows, A] and [rows, B], so every product is 2 * rows * A * B FLOPs.
    `rows` is NOT read from the text: that is the static buffer, padded
    over what the routing needs, and a kernel that skips the padding would
    read over 100% against it.  The caller gives the rows needed.

the rest of the expert layer
    Routing (scores, top-k, sort), the gather into the buffer and the
    scatter back, found by a dimension no other part of the step has: a
    buffer's rows (the first's or the exact path's), tokens * k pairs, or
    [tokens, experts]; and the shared
    expert's two up-projections by their width.  Its down-projection
    returns [tokens, hidden] like a dozen other instructions and is not
    told apart: `moe.ms_per_step` reads low by that much.
"""

from __future__ import annotations

import re

from benchmark.reduce import flash_cost, xplane

_WINDOWED = re.compile(r"^flash_(fwd|dq|dkv)_w(\d+)")
_KIND = {"fwd": "forward", "dq": "dq", "dkv": "dkv"}
_SHAPE = re.compile(r"(?:bf16|f32|f16|s32|u32|pred)\[([\d,]+)\]")
_BF16 = re.compile(r"bf16\[([\d,]+)\]")


def window_pairs(seq_len: int, window) -> int:
    """(query, key) pairs a causal call needs: row i sees min(i + 1,
    window) keys."""
    w = seq_len if window is None else min(window, seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def attention_call(instruction: str):
    """`(kind, BH, S, D, window or None)` of a flash kernel's instruction,
    or None for anything else."""
    if not flash_cost.is_kernel(instruction) or is_grouped(instruction):
        # (`classify` alone would read a grouped product's one
        # [experts, A, B] result as a dq call)
        return None
    call = flash_cost.classify(instruction)
    if call is None:
        return None
    m = _WINDOWED.match(xplane.op_name(instruction))
    return (*call, int(m.group(2)) if m else None)


def attention_cost(kind: str, bh: int, s: int, d: int, window,
                   itemsize: int = 2):
    """`(flops, bytes)` a causal call needs under `window`: `flash_cost`'s
    count with the pairs the mask leaves in place of the full square."""
    flops, nbytes = flash_cost.cost(kind, bh, s, d, causal=False,
                                    itemsize=itemsize)
    return flops * window_pairs(s, window) / (s * s), nbytes


def is_grouped(instruction: str) -> bool:
    return xplane.op_name(instruction).startswith("ragged-dot")


def grouped_call(instruction: str):
    """`(experts, A, B)` of a grouped product's instruction, or None (the
    metadata kernel, or a text without the operands' shapes)."""
    if not xplane.op_name(instruction).startswith("ragged-dot-none"):
        return None
    for dims in _BF16.findall(instruction):
        shape = tuple(map(int, dims.split(",")))
        if len(shape) == 3:
            return shape
    return None


def grouped_cost(rows: float, experts: int, a: int, b: int,
                 itemsize: int = 2):
    """`(flops, bytes)` of one grouped product over `rows` rows: each row
    through an [A, B] matrix; the rows' two sides and every expert's
    matrix moved once."""
    return (2.0 * rows * a * b,
            float(rows * (a + b) + experts * a * b) * itemsize)


def is_expert_layer(instruction: str, tokens: int, top_k: int, experts: int,
                    buffers, expert_width: int) -> bool:
    """Whether an instruction that runs belongs to the expert layer (see
    the module's docstring for what is and is not found)."""
    if is_grouped(instruction):
        return True
    if xplane.opcode(instruction) in ("sort", "topk"):
        return True
    result = instruction.split(" = ", 1)[-1].split("(", 1)[0]
    marks = {*buffers, tokens * top_k}
    for dims in _SHAPE.findall(result):
        shape = tuple(map(int, dims.split(",")))
        if marks & set(shape):
            return True
        flat = [d for d in shape if d != 1]
        n = 1
        for d in flat[:-1]:
            n *= d
        if flat and n == tokens and flat[-1] in (experts, top_k,
                                                 expert_width):
            return True
    return False
