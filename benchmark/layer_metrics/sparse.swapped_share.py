"""Sparse attention: percent of (row, layer) pairs whose keys the program
chose otherwise than the float32 reference would, in the run's reference
check (`benchmark/families/keye.py`: the best score a row left out lies
above the worst it took, in the reference's own scores; every such gap is
under the configuration's `index_selection_eps`, or `correct` is false).
Source: program counter."""


def read(ctx):
    records = [r for r in getattr(ctx.family, "selection", None) or ()
               if "key_swapped_share" in r]
    if not records:
        return None
    return 100.0 * sum(r["key_swapped_share"] for r in records) / len(
        records)
