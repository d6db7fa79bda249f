"""Sparse attention: milliseconds of a step inside the kernel that finds
every row's selection, `index_topk` (`benchmark/reduce/sparse_cost.py`):
the index scores of a block of rows into VMEM and the exact topk-th
largest of each by bisection.  Once a layer where the selection is kept
for the backward pass, twice where the layer's remat finds it again.
Source: device trace."""

from benchmark.reduce import sparse_cost


def read(ctx):
    spans = sparse_cost.kernel_spans(ctx.ops(0)).get("select")
    if not spans:
        return None
    return sum(spans) / ctx.n_steps / 1e6
