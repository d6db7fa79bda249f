"""PS staging: milliseconds of a round in which a `D2H` span of the
calling thread and some partition's `PUSH` or `PULL` span were both
open: the part of the copies off the device that the wire hides.  A
program that queues a round only after its last copy reads 0; one whose
`comm.json` has no `ROUND`, nothing.  Source: program span."""

import json
import os

from benchmark.reduce import intervals, program_spans


def _spans(events, keep) -> list:
    """Disjoint, sorted intervals of the events that `keep` accepts."""
    return intervals.union((e["ts"], e["ts"] + e.get("dur", 0))
                           for e in events if keep(e))


def hidden_us(events):
    """Per `ROUND` of worker 0 in a comm.json's `traceEvents`, the
    microseconds its own `D2H` spans share with the wire; None without
    a ROUND."""
    mine = [e for e in events if e.get("ph") == "X" and e.get("pid") == 0]
    rounds = {e["args"]["round"]: (e["ts"], e["ts"] + e["dur"])
              for e in mine if e.get("tid") == "ROUND"}
    if not rounds:
        return None
    wire = _spans(mine, lambda e: e.get("tid") in program_spans.WIRE
                  and (e.get("args") or {}).get("key") is not None)
    hidden = []
    for number, (lo, hi) in rounds.items():
        d2h = _spans(mine, lambda e: e.get("tid") == "D2H"
                     and e["args"]["round"] == number)
        hidden.append(intervals.overlap(intervals.clip(d2h, lo, hi),
                                        intervals.clip(wire, lo, hi)))
    return hidden


def read(ctx):
    path = os.path.join(ctx.dir, "0", "comm.json")
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        hidden = hidden_us(json.load(f)["traceEvents"])
    return None if hidden is None else sum(hidden) / len(hidden) / 1e3
