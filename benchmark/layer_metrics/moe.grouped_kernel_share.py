"""Expert layer: of the grouped products in the program's own map of its
compiled step (`bps.get_step_scopes()`: the instructions called
`ragged-dot-none*`, forward, rows' gradient and weights' gradient, every
pass), the percentage that are the program's Pallas kernels
(`ops/grouped_matmul.py`; their path ends in `pallas_call`) and not the
compiler's kernel for `lax.ragged_dot`, which a width the tile rule
refuses falls back to (an expert of 1856 = 14.5 x 128 did, before the
rule took multiples of 64).  100 says every product of the step runs the
program's kernels; it counts products, not their time.  Nothing where the
program gives no map or the step has no grouped product.  Source: program
counter."""


def read(ctx):
    import byteps_tpu as bps
    get = getattr(bps, "get_step_scopes", None)
    scopes = get() if get is not None else None
    if not scopes:
        return None
    grouped = [e for name, e in scopes.items()
               if name.startswith("ragged-dot-none")]
    if not grouped:
        return None
    own = sum(e.get("op_name", "").split(";")[0].rsplit("/", 1)[-1]
              == "pallas_call" for e in grouped)
    return 100.0 * own / len(grouped)
