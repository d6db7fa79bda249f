"""The PS plane's merged `comm.json` reduced to one step's communication
chain.

Input: the Chrome `traceEvents` the program writes when `BYTEPS_TRACE_ON`
is set and `bps.mark_step()` is called every step: worker spans on
pid = rank (tid QUEUE / ENCODE / PUSH / PULL / DECODE, one per partition
and stage, plus one STEP envelope per step) and server spans on
pid = 10000 + server (tid RECV / SUM / MERGE_WAIT / PUBLISH / PULL_SEND,
already moved onto the worker's clock).

For every STEP envelope the chain that decides the step is the partition
whose last span ends last.  Its stages split into what the worker's wire
and codec took and what the server took:

    wire    queue + encode + push_wire + pull_wire + decode
    server  server_recv + server_sum + merge_wait

where push_wire is the PUSH span less the server's RECV and SUM inside
it, and pull_wire the PULL span less the MERGE_WAIT inside it.  If the
chain's stages add up to more than the envelope (rounds overlapping
inside one step), all are scaled to fit.

The arithmetic is a copy of `byteps_tpu.common.trace_analysis.analyze`
(the part that yields `mean_breakdown_us`), kept here so that no later PR
can change the yardstick; the original is listed in PERF.md for removal.
"""

from __future__ import annotations

import functools
import json
import os

SERVER_PID_BASE = 10000
DEVICE_PID_BASE = 20000
WORKER_STAGES = ("QUEUE", "ENCODE", "PUSH", "PULL", "DECODE")
WIRE = ("queue", "encode", "push_wire", "pull_wire", "decode")
SERVER = ("server_recv", "server_sum", "merge_wait")


def _end(e) -> int:
    return e["ts"] + e.get("dur", 0)


def step_chains(events, worker: int = 0) -> list:
    """One dict per STEP envelope: `dur_us`, `partitions` (how many keys
    had spans in it) and the chain's components in microseconds."""
    spans = [e for e in events if e.get("ph") == "X"]
    steps = sorted((e for e in spans
                    if e.get("tid") == "STEP" and e.get("pid") == worker),
                   key=lambda e: e["ts"])
    mine = [e for e in spans if e.get("pid") == worker
            and e.get("tid") in WORKER_STAGES
            and (e.get("args") or {}).get("key") is not None]
    server = [e for e in spans
              if isinstance(e.get("pid"), int)
              and SERVER_PID_BASE <= e["pid"] < DEVICE_PID_BASE]
    rows = []
    for st in steps:
        t0, t1 = st["ts"], _end(st)
        by_key: dict = {}
        for e in mine:
            if e["ts"] < t1 and _end(e) > t0:
                # a stage may repeat within a step: the last span decides
                by_key.setdefault(e["args"]["key"], {})[e["tid"]] = e
        if not by_key:
            continue
        key = max(by_key, key=lambda k: max(map(_end, by_key[k].values())))
        chain = by_key[key]

        def worker_us(stage):
            return int(chain[stage].get("dur", 0)) if stage in chain else 0

        def server_us(stage):
            return max((int(e.get("dur", 0)) for e in server
                        if e.get("tid") == stage
                        and (e.get("args") or {}).get("key") == key
                        and (e.get("args") or {}).get("worker") == worker
                        and e["ts"] < t1 and _end(e) > t0), default=0)

        c = {"queue": worker_us("QUEUE"), "encode": worker_us("ENCODE"),
             "decode": worker_us("DECODE"),
             "server_recv": server_us("RECV"),
             "server_sum": server_us("SUM"),
             "merge_wait": server_us("MERGE_WAIT")}
        c["push_wire"] = max(
            0, worker_us("PUSH") - c["server_recv"] - c["server_sum"])
        c["pull_wire"] = max(0, worker_us("PULL") - c["merge_wait"])
        chain_us = sum(c.values())
        if chain_us > t1 - t0:
            c = {k: v * (t1 - t0) / chain_us for k, v in c.items()}
        rows.append({"dur_us": t1 - t0, "partitions": len(by_key), **c})
    return rows


def mean_us(rows: list, components) -> float:
    """Mean over the steps of the sum of `components`."""
    return sum(r[c] for r in rows for c in components) / len(rows)


@functools.lru_cache(maxsize=2)
def rows(trace_dir: str, local_rank: int = 0) -> tuple:
    """`step_chains` of `<trace_dir>/<local_rank>/comm.json`, where the
    program dumps its merged trace; empty where there is none.  Kept for
    the next reader of the same file (callers only read it)."""
    path = os.path.join(trace_dir, str(local_rank), "comm.json")
    if not os.path.isfile(path):
        return ()
    with open(path) as f:
        return tuple(step_chains(json.load(f)["traceEvents"]))
