"""Mamba-2 mixer: of the step's convolution scopes (`<family>.mamba.conv`
with the pass it runs in: forward, recompute, backward) in the program's
own map of its compiled step (`bps.get_step_scopes()`), the percentage
that hold the program's Pallas kernel (`ops/short_conv.py` `mamba_conv`:
an instruction called `mamba_conv_*` whose path ends in `pallas_call`).
0 where the step has the scope and the compiler's own shifted passes
under it (the `jnp` form, until PR 56), 100 where every pass of every
mixer calls the kernel; it counts scopes, not time (`mamba.conv_ms` has
that).  Nothing where the program gives no map or the step has no such
scope.  Source: program counter."""

import re

_SCOPE = re.compile(r"\.mamba\.conv$")


def read(ctx):
    import byteps_tpu as bps
    get = getattr(bps, "get_step_scopes", None)
    scopes = get() if get is not None else None
    if not scopes:
        return None
    held = {}
    for name, e in scopes.items():
        scope = e.get("scope") or ""
        if not _SCOPE.search(scope):
            continue
        op = e.get("op_name", "").split(";")[0].rsplit("/", 1)[-1]
        key = (scope, e.get("pass"))
        held[key] = held.get(key, False) or (
            name.startswith("mamba_conv_") and op == "pallas_call")
    if not held:
        return None
    return 100.0 * sum(held.values()) / len(held)
