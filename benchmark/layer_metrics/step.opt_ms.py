"""Model step: milliseconds of a step chip 0 spends in the optimizer (under
the trainer's `byteps.optimizer` scope: the update, the parameters' new
values, and at dp > 1 the exchange nested in it).
The program's own map of its compiled step (`bps.get_step_scopes()`) laid
over the trace, each instruction with its own time
(`benchmark/reduce/scopes.py`); with the other passes and "other" it
partitions the chip's busy time, `step.device_ms`.  A program without the
map reads nothing.  Source: program span."""

from benchmark.reduce import scopes


def read(ctx):
    return scopes.pass_ms(ctx, "optimizer")
