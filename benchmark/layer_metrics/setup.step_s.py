"""Entry / trainer: seconds of set-up in tracing, lowering and compiling
the train step itself: the union of the compile log's records of every
kind with `cause="train_step"` that ended before `steady_at`.  The part of
set-up a change to a model or a kernel moves.  Source: program span."""

from benchmark.reduce import compile_log


def read(ctx):
    return compile_log.setup_seconds(cause="train_step")
