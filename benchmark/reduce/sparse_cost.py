"""What attention over selected keys has to compute and move, and which
instructions of a device trace are its kernels.

The kernels name themselves (`ops/sparse_attention.py`): `index_topk`
finds every row's selection, `sparse_fwd`, `sparse_dq` and `sparse_dkv`
are the attention under it.  The work counted here is what the SELECTION
leaves, whatever realises it:

the index scores
    One pass scores every causal pair once: 2 FLOPs a multiply-add over
    J indexer heads of Di.  Bytes: the indexer's queries, key and weights
    read once, two numbers a row written.  (A realisation that computes
    the scores again inside its attention kernels, as this one does, has
    that time in `sparse.attn_ms_per_step` and no credit for it.)

the attention
    The pairs a row SELECTS, min(t + 1, topk), summed over the rows, by H
    heads of D, 2 FLOPs a multiply-add, by the matmuls of the call's kind
    (`flash_cost`'s count: forward 2, dq 3, dkv 4).  Bytes: q, k, v, o and
    their gradients once each, keys and values over Hkv heads, the rows'
    statistics in float32.  A mask over dense tiles does the causal
    triangle's work and reads low against this; a gather of the selected
    keys can approach it; nothing reads over 100%.
"""

from __future__ import annotations

from benchmark.reduce import xplane

SELECT = "index_topk"
ATTENTION = {"sparse_fwd": "forward", "sparse_dq": "dq", "sparse_dkv": "dkv"}
# kind: (matmuls, [H, S, D] arrays, [Hkv, S, D] arrays, [H, S] f32 rows)
_KINDS = {"forward": (2, 2, 2, 1), "dq": (3, 3, 2, 2), "dkv": (4, 2, 4, 2)}


def kernel(instruction: str):
    """`"select"`, `"forward"`, `"dq"`, `"dkv"` or None: which of the
    sparse attention's kernels an instruction of the trace is."""
    name = xplane.op_name(instruction)
    if name.startswith(SELECT):
        return "select"
    for prefix, kind in ATTENTION.items():
        if name.startswith(prefix):
            return kind
    return None


def causal_pairs(s: int) -> int:
    return s * (s + 1) // 2


def selected_pairs(s: int, topk: int) -> int:
    """Sum over the rows of min(t + 1, topk)."""
    full = min(topk, s)
    return full * (full + 1) // 2 + (s - full) * full


def index_cost(s: int, heads: int, dim: int, itemsize: int = 2):
    """`(flops, bytes)` of one pass of index scores over a sequence."""
    return (2.0 * causal_pairs(s) * heads * dim,
            float((heads + 1) * s * dim * itemsize + heads * s * 4 + s * 8))


def attention_cost(kind: str, s: int, topk: int, heads: int, kv_heads: int,
                   d: int, itemsize: int = 2):
    """`(flops, bytes)` one attention call of `kind` needs of a
    sequence."""
    matmuls, wide, narrow, rows = _KINDS[kind]
    return (matmuls * 2.0 * selected_pairs(s, topk) * heads * d,
            float((wide * heads + narrow * kv_heads) * s * d * itemsize
                  + rows * heads * s * 4))


def kernel_spans(ops) -> dict:
    """`{kind: [nanoseconds of each call]}` over a chip's instructions."""
    out: dict = {}
    for name, start, end in ops:
        kind = kernel(name)
        if kind is not None:
            out.setdefault(kind, []).append(end - start)
    return out
