"""Entry / trainer: of `setup.programs`, those the persistent cache did
not hold and that took long enough for it to keep: `miss` (written now:
the next run hits) and `uncached` (never kept: every run compiles it).
In a warm run, what no cache will ever hold.  Source: program counter."""

from benchmark.reduce import compile_log


def read(ctx):
    return compile_log.setup_count(answers=("miss", "uncached"))
