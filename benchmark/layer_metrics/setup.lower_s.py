"""Entry / trainer: seconds of set-up in which a jaxpr was lowered to
MLIR, Pallas bodies to Mosaic's among it: the union of the compile log's
`LOWER` records that ended before `steady_at`.  Source: program span."""

from benchmark.reduce import compile_log


def read(ctx):
    return compile_log.setup_seconds("LOWER")
