"""What a call of the delta-rule scan has to compute and move, from its
name and the family's shapes, beside `ssd_cost.py`.

`ops/kda.py` names its two calls `kda_fwd_c<C>` and `kda_bwd_c<C>`.  The
CHUNKED form at chunk C, a head of K key channels and V value channels, a
chunk (2 FLOPs a multiply-add):

    K S0, Q S0 and the state's update          3 C K V
    k k^T and q k^T, the pairwise decays in    2 C C K
    the solve's product and A_qk U             2 C C V

so a forward call is 2 (3 C K V + 2 C C K + 2 C C V) FLOPs a chunk and
head; a backward call forms the chunk again and its transpose: three
times that, counted as needed by the call (as `flash_cost.py` counts a
backward kernel's S and dP).  The inverse's doubling products, the
exponentials and the l2 norms are not counted: the same work whichever
form runs.  Bytes are each operand read once and each result written
once: q, k, v, o at the activations' size, g in float32, beta, and the
chunk states [S/C, H, V, K] float32 written forward and read backward;
backward q, k, v, do in and dq, dk, dv out, g in and dg out.
"""

from __future__ import annotations

import re

from benchmark.reduce import flash_cost, xplane

_NAME = re.compile(r"kda_(fwd|bwd)_c(\d+)")


def call(instruction: str):
    """`(kind, chunk)` of one of the scan's kernels, or None."""
    if not flash_cost.is_kernel(instruction):
        return None
    m = _NAME.search(xplane.op_name(instruction))
    return (m.group(1), int(m.group(2))) if m else None


def cost(kind: str, tokens: int, heads: int, key_dim: int, value_dim: int,
         chunk: int, itemsize: int = 2):
    """`(flops, bytes)` one call over `tokens` positions needs."""
    C, K, V = chunk, key_dim, value_dim
    forward = (tokens // C) * heads * 2.0 * (
        3 * C * K * V + 2 * C * C * K + 2 * C * C * V)
    wide_k, wide_v = tokens * heads * K, tokens * heads * V
    states = (tokens // C) * heads * K * V * 4
    beta = tokens * heads * 4
    if kind == "fwd":
        return forward, float((2 * wide_k + 2 * wide_v) * itemsize
                              + wide_k * 4 + beta + states)
    return 3.0 * forward, float((4 * wide_k + 3 * wide_v) * itemsize
                                + 2 * wide_k * 4 + 2 * beta + states)
