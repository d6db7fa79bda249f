"""Sparse attention: percent of the causal (query, key) pairs that the
attention kernels KEPT, counted by the forward kernel itself a row
(`ops/sparse_attention.py` `count`) in the run's reference check, over
the layers: sum of min(t + 1, topk) over the causal pairs where selection
and attention agree (12.1% at 32,768 rows and topk 2,048), and `correct`
is false where a row kept another number.  Source: program counter."""


def read(ctx):
    records = [r for r in getattr(ctx.family, "selection", None) or ()
               if "selected_share" in r]
    if not records:
        return None
    return 100.0 * sum(r["selected_share"] for r in records) / len(records)
