"""Entry / trainer: how many times set-up compiled the train step: the
compile log's `COMPILE` records with `cause="train_step"` that ended
before `steady_at`.  Source: program counter."""

from benchmark.reduce import compile_log


def read(ctx):
    return compile_log.setup_count(cause="train_step")
