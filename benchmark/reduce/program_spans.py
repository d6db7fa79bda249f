"""The round as the program spans it from inside, reduced per round.

Input, both optional: the merged `comm.json` of a PS job, where a program
that has them writes the calling thread's stage spans of every
`bps.push_pull_tree` call (pid = rank; tid `ROUND` around the call and,
under it, `PACK`, `D2H`, `STAGE`, `WAIT`, `H2D`, `SCATTER`, `FREE`; each
carries `args.round`, the number of its `ROUND`) beside the dispatcher's
per-partition spans (`PUSH`, `PULL`, ...); and the profiler's
`.xplane.pb`, where the same program enters
`jax.profiler.TraceAnnotation("byteps.round")` for the extent of every
`ROUND`.  A trace of a program without them reduces to None: no metric,
not a zero.

A stage counts only if its `round` names a `ROUND` of the file: the
program writes the same stages with round 0 outside `push_pull_tree`.

comm.json is on the program's `steady_clock` (microseconds), the xplane
on the profiler's (nanoseconds from its start); `exposed_ns` cuts the
chip's idle time with the annotations, on the xplane's clock alone, so
no reader here needs the offset between the two (the program's
`tools/trace_analyze.py --xplane` gives it).
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os

from benchmark.reduce import intervals, xplane

STAGES = ("PACK", "D2H", "STAGE", "WAIT", "H2D", "SCATTER", "FREE")
WIRE = ("PUSH", "PULL")
ANNOTATION = "byteps.round"


@dataclasses.dataclass(frozen=True)
class Rounds:
    spans: tuple        # (start_us, end_us) of every ROUND, by start
    stage_us: dict      # stage -> microseconds summed over the rounds
    wire_busy_us: int   # time some partition's PUSH or PULL was open

    def mean_ms(self, *stages: str) -> float:
        return sum(self.stage_us[s] for s in stages) / len(self.spans) / 1e3


def reduce(events, worker: int = 0):
    """`Rounds` of a comm.json's `traceEvents`, or None without a ROUND."""
    mine = [e for e in events if e.get("ph") == "X"
            and e.get("pid") == worker]
    rounds = sorted((e for e in mine if e.get("tid") == "ROUND"),
                    key=lambda e: e["ts"])
    if not rounds:
        return None
    ids = {e["args"]["round"] for e in rounds}
    spans = tuple((e["ts"], e["ts"] + e["dur"]) for e in rounds)
    stage_us = dict.fromkeys(STAGES, 0)
    for e in mine:
        if e.get("tid") in STAGES and e["args"]["round"] in ids:
            stage_us[e["tid"]] += e["dur"]
    wire = [(e["ts"], e["ts"] + e.get("dur", 0)) for e in mine
            if e.get("tid") in WIRE
            and (e.get("args") or {}).get("key") is not None]
    busy = sum(intervals.total(intervals.union(intervals.clip(wire, lo, hi)))
               for lo, hi in spans)
    return Rounds(spans=spans, stage_us=stage_us, wire_busy_us=busy)


@functools.lru_cache(maxsize=2)
def rounds(trace_dir: str, local_rank: int = 0):
    """`reduce` of `<trace_dir>/<local_rank>/comm.json`; None where there
    is no file or no ROUND in it."""
    path = os.path.join(trace_dir, str(local_rank), "comm.json")
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        return reduce(json.load(f)["traceEvents"])


@functools.lru_cache(maxsize=2)
def annotations(trace_dir: str) -> tuple:
    """`(start_ns, end_ns)` of every `byteps.round` annotation in the
    profiler's trace under `trace_dir`, by start; empty where there is
    no trace or it holds none."""
    path = xplane.find(trace_dir)
    if path is None:
        return ()
    host = xplane.read(path, host_prefix="byteps.").host
    return tuple(sorted((s, e) for n, s, e in host if n == ANNOTATION))


def exposed_ns(trace_dir: str, idle) -> float:
    """Nanoseconds of the disjoint, sorted `idle` intervals (xplane
    clock) that lie inside a `byteps.round` annotation; None without
    one."""
    marked = annotations(trace_dir)
    if not marked:
        return None
    return intervals.overlap(idle, intervals.union(marked))
