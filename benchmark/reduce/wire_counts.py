"""What the PS wire waited for, reduced over a trace's rounds, and the
floor it is read against.

Input, both optional, beside each other under `<trace_dir>/<rank>/`:
`comm.json`, where a program that has them writes into every `ROUND`
span's args what its session's lanes counted while the round was open
(`send_calls`, `recv_calls`, `send_lock_wait_us`, `send_us`, `recv_us`,
`recv_first_byte_us` over `pulls` pulls, `lanes` and the list
`lane_busy_us`, beside the `bytes_out` and `bytes_in` it had); and
`wire_floor.json`, which the same program's worker leaves at shutdown:
the bytes a second this host moves between that process and another
over the session's kind and number of lanes (`out`, `in`, `duplex`).

A trace of a program without the counts reduces to None, and one
without the file has no floor: no metric, not a zero.  The wire's rate
is read only beside the counts, so the metrics of this file come
together or not at all.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os

from benchmark.reduce import program_spans

SUMMED = ("bytes_out", "bytes_in", "send_calls", "recv_calls",
          "send_lock_wait_us", "send_us", "recv_us", "recv_first_byte_us",
          "pulls")


@dataclasses.dataclass(frozen=True)
class Wire:
    rounds: int             # ROUNDs that carry the counts
    total: dict             # each of SUMMED, summed over them
    lane_busy_us: tuple     # by lane, summed over them
    wire_busy_us: int       # time some partition's PUSH or PULL was open

    @property
    def bytes(self) -> int:
        return self.total["bytes_out"] + self.total["bytes_in"]

    @property
    def GB_per_s(self) -> float:
        return self.bytes / self.wire_busy_us / 1e3

    def per_round_ms(self, key: str) -> float:
        return self.total[key] / self.rounds / 1e3


def reduce(events, worker: int = 0):
    """`Wire` of a comm.json's `traceEvents`; None unless every `ROUND`
    of `worker` carries the counts and some partition was on the wire."""
    args = [e["args"] for e in events if e.get("ph") == "X"
            and e.get("pid") == worker and e.get("tid") == "ROUND"]
    spans = program_spans.reduce(events, worker)
    if not args or not all("send_calls" in a for a in args) \
            or not spans.wire_busy_us:
        return None
    lanes = max(a["lanes"] for a in args)
    return Wire(
        rounds=len(args),
        total={k: sum(a[k] for a in args) for k in SUMMED},
        lane_busy_us=tuple(
            sum(a["lane_busy_us"][i] for a in args
                if i < len(a["lane_busy_us"])) for i in range(lanes)),
        wire_busy_us=spans.wire_busy_us)


@functools.lru_cache(maxsize=2)
def wire(trace_dir: str, local_rank: int = 0):
    """`reduce` of `<trace_dir>/<local_rank>/comm.json`, or None."""
    path = os.path.join(trace_dir, str(local_rank), "comm.json")
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        return reduce(json.load(f)["traceEvents"])


@functools.lru_cache(maxsize=2)
def floor(trace_dir: str, local_rank: int = 0):
    """`<trace_dir>/<local_rank>/wire_floor.json` as written, or None."""
    path = os.path.join(trace_dir, str(local_rank), "wire_floor.json")
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        return json.load(f)
