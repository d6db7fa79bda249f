"""PS staging: what of `ps.round_ms` (the job's timer: call, then pulled
tree ready) no stage span covers: Python between the spans inside the
`ROUND`, and the tail after `push_pull_tree` returns while the copies
back and the scatter programs finish.  By construction
d2h + stage + wait + h2d + free + unspanned = `ps.round_ms`.  Source:
program span."""

from benchmark.harness.readers import reader

_STAGES = ("ps.d2h_ms", "ps.stage_ms", "ps.wait_ms", "ps.h2d_ms",
           "ps.free_ms")


def read(ctx):
    values = [reader(name)(ctx) for name in ("ps.round_ms",) + _STAGES]
    if any(v is None for v in values):
        return None
    return values[0] - sum(values[1:])
