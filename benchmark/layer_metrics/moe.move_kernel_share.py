"""Expert layer: of the instructions that move rows under the `gather` and
`scatter` children of the family's `<family>.moe` scope (the exact path's
too), in the program's own map of its compiled step
(`bps.get_step_scopes()`), the percentage that are the program's Pallas
kernel (`ops/moe_rows.py`: an instruction called `moe_rows_*` whose path
ends in `pallas_call`) and not the compiler's `gather` or `scatter-add`
of whole rows (what the layer moved its rows with until PR 52).  An
instruction moves rows if it is that kernel, or its path ends in a
`gather` or a `scatter` primitive directly under one of those two scopes;
the layer's gathers of single weights lie a scope deeper (`.../weights`)
and are not rows.  100 says every move of the step is the kernel; it
counts instructions, not their time (`moe.move_ms` has that).  0 where
the step has those scopes and no kernel; nothing where the program gives
no map or the step has no expert layer.  Source: program counter."""

import re

_SCOPE = re.compile(r"\.moe(/exact)?/(gather|scatter)$")
_XLA = ("gather", "scatter", "scatter-add", "scatter_add")


def read(ctx):
    import byteps_tpu as bps
    get = getattr(bps, "get_step_scopes", None)
    scopes = get() if get is not None else None
    if not scopes:
        return None
    under = [(name, e.get("op_name", "").split(";")[0].rsplit("/", 1)[-1])
             for name, e in scopes.items()
             if _SCOPE.search(e.get("scope") or "")]
    if not under:
        return None
    own = sum(name.startswith("moe_rows") and op == "pallas_call"
              for name, op in under)
    theirs = sum(op in _XLA for _, op in under)
    return 100.0 * own / (own + theirs) if own + theirs else 0.0
