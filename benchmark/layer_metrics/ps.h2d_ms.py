"""PS staging: milliseconds a round's calling thread spent in `H2D` and
`SCATTER` spans.  Both are dispatch time: `H2D` is the `jnp.asarray`
call that starts a unit's copy back to the device (and whatever part of
it is synchronous), `SCATTER` the dispatch of decompress, average,
slice, reshape and cast.  The copy itself runs on after the span closes,
under later `WAIT`s and the tail in `ps.unspanned_ms`, so this value
cannot see it: a change to how pulls land is read from the device trace
(the xplane's host-to-device transfer events inside `byteps.round`), a
metric no PR has added yet.  Source: program span."""

from benchmark.reduce import program_spans


def read(ctx):
    rounds = program_spans.rounds(ctx.dir)
    return rounds and rounds.mean_ms("H2D", "SCATTER")
