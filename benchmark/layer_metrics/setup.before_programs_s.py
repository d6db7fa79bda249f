"""Entry / trainer: seconds from the start of the process to the first
program it traced: the imports, the core's build or load, reaching the
chip.  `process_start` of the program's compile log to its first record's
start.  Source: program span."""

from benchmark.reduce import compile_log


def read(ctx):
    log = compile_log.snapshot()
    if log is None or log.get("process_start") is None \
            or not log["records"]:
        return None
    return min(r["start"] for r in log["records"]) - log["process_start"]
