"""Entry / trainer: seconds of set-up in the backend's compile or the
persistent cache's retrieval: the union of the compile log's `COMPILE`
records that ended before `steady_at`.  Source: program span."""

from benchmark.reduce import compile_log


def read(ctx):
    return compile_log.setup_seconds("COMPILE")
