"""Entry / trainer: programs the process compiled or retrieved during
set-up: the compile log's `COMPILE` records that ended before
`steady_at`.  Source: program counter."""

from benchmark.reduce import compile_log


def read(ctx):
    return compile_log.setup_count()
