"""Sparse attention: milliseconds of a step inside the attention kernels
over the selected keys, `sparse_fwd`, `sparse_dq` and `sparse_dkv`
(`benchmark/reduce/sparse_cost.py`), the forward pass's recompute under
remat included.  In this realisation a kernel computes its tiles' index
scores again to mask by them, and that time is here.  Source: device
trace."""

from benchmark.reduce import sparse_cost


def read(ctx):
    spans = sparse_cost.kernel_spans(ctx.ops(0))
    took = sum(sum(spans.get(kind, ())) for kind in ("forward", "dq", "dkv"))
    return took / ctx.n_steps / 1e6 if took else None
