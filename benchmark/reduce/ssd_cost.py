"""What the program's chunked state-space scan (`ops/ssd.py`) has to
compute and move, from its shapes, and which instructions of a device
trace belong to it, to the Mamba-2 mixer around it and to the attention
layers beside it.

The scan's kernels are Mosaic custom calls that the program NAMES:
`ssd_fwd_c<Q>` and `ssd_bwd_c<Q>`, Q the chunk (an instruction is called
after the name with what JAX puts around it, `jvp_ssd_fwd_c256_`,
`transpose_jvp_ssd_bwd_c256__`, so the name is searched for, not matched
at the start).  Under per-layer remat a layer's forward kernel runs twice
a step, once in the forward pass and once as recomputation, then its
backward kernel once; each is a call like any other.

The chunked form, a chunk of Q positions, T tokens, H heads of size P in
G groups, state N (2 FLOPs a multiply-add):

    a head and chunk:   inside    (decay * C B^T) (dt x)     2 Q Q P
                        carried   C S_prev^T                 2 Q N P
                        state     (decay dt x)^T B           2 Q P N
    a group and chunk:  C B^T                                2 Q Q N

    forward   = T/Q (H (2QQP + 4QPN) + G 2QQN)
    backward  = T/Q (2 H (2QQP + 4QPN) + 3 G 2QQN)

the backward pass needing the gradient of every product with respect to
both operands (twice the forward's FLOPs) and C B^T once more, to rebuild
the decay-weighted matrix it does not keep.  What the kernel computes
beyond that (C B^T once a block of 8 heads and not once a group; the
chunk's own result again, for the gradient of the cumulative sum) is not
needed by the algorithm and not counted, so a kernel that stops doing it
reads higher.  The exponentials and the elementwise passes over the
[Q, Q] matrices are not counted either, as `flash_cost` leaves the
softmax's out; they are most of what the vector unit does here.  Bytes
are each operand read once and each result written once: x and y (and
dy, dx) in the compute dtype, dt and the cumulative sum (and their
gradients) and the chunk states in float32, B and C once a group.

The mixer around the scan is found by the dimensions only it has, in
instructions that touch the step's tokens (`is_mixer`): the `in_proj`'s
width, the convolution's channels, the inner width.  The attention
layers' flash kernels are the Mosaic calls that are left
(`attention_call`).
"""

from __future__ import annotations

import re

from benchmark.reduce import flash_cost, xplane

_NAME = re.compile(r"ssd_(fwd|bwd)_c(\d+)")
_KIND = {"fwd": "forward", "bwd": "backward"}
_SHAPE = re.compile(r"(?:bf16|f32|f16|s32|u32|pred)\[([\d,]+)\]")


def scan_call(instruction: str):
    """`(kind, chunk)` of one of the scan's kernels, or None."""
    if not flash_cost.is_kernel(instruction):
        return None
    m = _NAME.search(xplane.op_name(instruction))
    return (_KIND[m.group(1)], int(m.group(2))) if m else None


def _products(tokens, heads, head_dim, state, groups, chunk):
    """FLOPs of the per-head products and of C B^T, over the sequence."""
    chunks = tokens // chunk
    per_head = 2.0 * chunk * chunk * head_dim + 4.0 * chunk * head_dim * state
    return (chunks * heads * per_head,
            chunks * groups * 2.0 * chunk * chunk * state)


def cost(kind: str, tokens: int, heads: int, head_dim: int, state: int,
         groups: int, chunk: int, itemsize: int = 2):
    """`(flops, bytes)` one call over `tokens` positions needs."""
    head, group = _products(tokens, heads, head_dim, state, groups, chunk)
    wide = tokens * heads * head_dim * itemsize          # x, y, dy, dx
    rows = tokens * heads * 4                            # dt, cs, ...
    states = (tokens // chunk) * heads * head_dim * state * 4
    if kind == "forward":
        return (head + group,
                float(2 * wide + 2 * rows + states
                      + 2 * tokens * groups * state * itemsize))
    if kind == "backward":
        return (2 * head + 3 * group,
                float(3 * wide + 4 * rows + states
                      + 2 * tokens * groups * state * (itemsize + 4)))
    raise ValueError(f"kind={kind!r}")


def model_flops(tokens: int, heads: int, head_dim: int, state: int,
                groups: int, chunk: int) -> float:
    """The scan's share of the MODEL FLOPs of one layer on one sequence:
    forward and backward, the recomputed C B^T left out (3 times the
    forward's)."""
    head, group = _products(tokens, heads, head_dim, state, groups, chunk)
    return 3.0 * (head + group)


def _dims(text: str):
    return [set(map(int, dims.split(","))) for dims in _SHAPE.findall(text)]


def is_mixer(instruction: str, marks, tokens) -> bool:
    """Whether an instruction that runs belongs to a Mamba-2 mixer: one of
    the scan's kernels, or a result with a dimension from `marks`, the
    sizes only the mixer has (`in_proj`'s width, the convolution's
    channels, the inner width), in an instruction that TOUCHES THE STEP'S
    TOKENS: its result or one of its operands has a dimension from
    `tokens` (the positions of a sequence, the rows of a step).  Found so:
    `in_proj`'s product and its weight's gradient (whose result is the
    whole stack of the run's gradients, [layers, hidden, width], written
    a layer at a time, and whose operands are activations), the
    convolution and its gradients, the split, the gating, the gated norm,
    `out_proj`'s gradients.  NOT found:

      - what only has the weights' shapes and touches no token: the
        optimizer's update of `in_proj_w`, `out_proj_w`, `conv_w` and
        `gate_norm` (tuples of whole stacks with both moments), the
        casts of those stacks to the compute dtype, a layer's slice of
        such a stack and the zeros the stacks of gradients start from:
        14.9 ms a step of the cell's 487 (chip, PR 34), the optimizer's
        and the step's time, not the mixer's;
      - `out_proj`'s forward product and `in_proj`'s gradient of its
        input, whose results are [tokens, hidden] like the MLP's and
        attention's; the per-head rows (dt's softplus, the cumulative
        sums) and the transposes around the scan where they are
        instructions of their own ([batch, heads, positions, head size]),
        whose dimensions attention's heads share.  The metric reads low
        by that much."""
    if scan_call(instruction):
        return True
    # what stands before the opcode: one shape, or a tuple of them
    result = instruction.split(" = ", 1)[-1].split(
        f" {xplane.opcode(instruction)}(", 1)[0]
    return (any(marks & dims for dims in _dims(result))
            and any(tokens & dims for dims in _dims(instruction)))


def attention_call(instruction: str):
    """`(kind, BH, S, D)` of one of the attention layers' flash kernels,
    or None: a Mosaic call that is not the scan's and returns what one of
    the three flash kernels returns (`flash_cost.classify`).  The model's
    attention is causal, so a call is costed at the causal triangle."""
    if not flash_cost.is_kernel(instruction) or scan_call(instruction):
        return None
    return flash_cost.classify(instruction)
