"""Sparse attention: milliseconds of a step under the `index` child of
the attention half (`<family>.attn*/index`): the indexer's three
projections of the layer's input, its key's norm and the rotary passes
on its queries and key, forward and recompute (it has no backward pass).
The score products themselves have no instruction of their own in this
realisation: they run inside `index_topk` (`sparse.select_ms_per_step`)
and inside the attention kernels (`sparse.attn_ms_per_step`).  From the
program's map of its step (`benchmark/reduce/scopes.py`).  Source: program
span."""

from benchmark.reduce import scopes


def read(ctx):
    if not hasattr(getattr(ctx.family, "cfg", None), "index_topk"):
        return None         # another family's attention has no such child
    return scopes.scope_ms(ctx, r"\.attn", children=("index",))
