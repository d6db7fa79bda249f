"""Expert layer: percent of (token, expert layer) pairs whose eight
experts the program chose otherwise than the float32 reference would, in
the run's reference check (`benchmark/families/afmoe.py`; every such swap
was between scores closer than the configuration's `selection_eps`, or
`correct` is false).  Source: program counter."""


def read(ctx):
    records = getattr(ctx.family, "selection", None)
    if not records:
        return None
    return 100.0 * sum(r["swapped_share"] for r in records) / len(records)
