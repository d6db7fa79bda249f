"""Entry / trainer: seconds of set-up in which Python built a jaxpr: the
union of the compile log's `TRACE` records that ended before the
program's `steady_at`.  Source: program span."""

from benchmark.reduce import compile_log


def read(ctx):
    return compile_log.setup_seconds("TRACE")
