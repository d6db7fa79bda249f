"""Reductions of the program's compile log (`bps.get_compile_log()`:
what the process traced, lowered and compiled, a record an outermost span
of a stage) to the parts of set-up.  Set-up is what ended before the
log's `steady_at`, which the program sets itself.  A time is the union of
its records' spans on the clock, never a sum of nested or concurrent
ones."""

from __future__ import annotations

from benchmark.reduce import intervals


def snapshot():
    """The log of this process, or None from a program that keeps none
    or whose set-up never ended."""
    import byteps_tpu as bps
    get = getattr(bps, "get_compile_log", None)
    log = get() if get is not None else None
    if not log or log.get("steady_at") is None:
        return None
    return log


def setup_records(log: dict, kind=None, cause=None) -> list:
    return [r for r in log["records"]
            if r["end"] <= log["steady_at"]
            and (kind is None or r["kind"] == kind)
            and (cause is None or r.get("cause") == cause)]


def seconds(records) -> float:
    return intervals.total(intervals.union(
        (r["start"], r["end"]) for r in records))


def setup_seconds(kind=None, cause=None):
    """Seconds of set-up in the records of `kind` and `cause`; None where
    there is no log or no such record."""
    log = snapshot()
    records = setup_records(log, kind, cause) if log else []
    return seconds(records) if records else None


def setup_count(cause=None, answers=None):
    """COMPILE records of set-up, of `cause` and with one of the cache's
    `answers` where given; None where there is no log."""
    log = snapshot()
    if log is None:
        return None
    return sum(1 for r in setup_records(log, "COMPILE", cause)
               if answers is None or r.get("cache") in answers)
