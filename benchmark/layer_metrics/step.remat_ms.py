"""Model step: milliseconds of a step chip 0 spends in the recomputation of a
checkpointed layer's forward pass inside the backward pass (under
`rematted_computation`): what whole-layer remat costs.
The program's own map of its compiled step (`bps.get_step_scopes()`) laid
over the trace, each instruction with its own time
(`benchmark/reduce/scopes.py`); with the other passes and "other" it
partitions the chip's busy time, `step.device_ms`.  A program without the
map reads nothing.  Source: program span."""

from benchmark.reduce import scopes


def read(ctx):
    return scopes.pass_ms(ctx, "recompute")
