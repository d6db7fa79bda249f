"""Critical-path analysis of the merged distributed trace.

Input: Chrome ``traceEvents`` from the merged ``comm.json`` — worker spans
(pid = rank; tid = QUEUE/ENCODE/PUSH/PULL/DECODE plus the STEP envelopes)
and server spans (pid = SERVER_PID_BASE + server index; tid = RECV/SUM/
MERGE_WAIT/PUBLISH/PULL_SEND, already offset-corrected onto the worker's
clock by ``PSSession.fetch_server_trace``).

For each STEP envelope the analyzer finds the step's communication
critical path — the partition chain whose pull lands last — and splits the
step's wall time into attributable components:

  queue        partition sat in the dispatcher's priority queue
  encode       worker-side wire compression (codec pool / inline)
  server_recv  push frame sat in the server's engine queue
  server_sum   server decompress + merge work for our push
  merge_wait   round held open waiting for the other workers (stragglers)
  push_wire    push dispatch -> server ack, minus the server residency
  pull_wire    pull issue -> data, minus our merge wait
  decode       worker-side decode of a recompressed pull payload
  other        everything the communication chain does not explain
               (compute, framework overhead)

The components are defined to PARTITION the step: ``other`` absorbs the
remainder, and if measured chain components ever exceed the step envelope
(overlapping rounds inside one step) they are scaled down proportionally —
so ``sum(breakdown) == step duration`` always holds exactly.

Beside the chain, ``round_breakdown_us`` is the round as the calling
thread passed through it: the mean per ``ROUND`` span (one
``push_pull_tree`` call in PS mode, common/stage_spans.py) of each stage
span under it, and ``unspanned``, what of the ``ROUND`` no stage covers
(Python between the spans).  The stages of a round do not overlap, so
``sum(stages) + unspanned == round`` exactly.  ``round_wire`` is what the
session's lanes counted inside those rounds, mean per ``ROUND``: the
socket calls, the time in them and waiting for a send lock, the pulls'
wait for their first byte, and each lane's time with bytes outstanding
(docs/timeline.md, "What the wire waited for"); empty for a program
whose ``ROUND`` carries none.

``compile_log`` is what the file holds of the process's compile log
(utils/compile_cache.py): its totals and the longest of its spans inside
the traced window, where a recompile lies beside the step or round it
stretched.

``update_critical_path_gauges`` feeds the per-component means into the
PR-4 telemetry registry as ``bps_step_critical_path_seconds{component=…}``
(plus ``bps_step_straggler_wait_seconds{worker=…}``), so ``tools/bps_top``
and the Prometheus endpoint surface the breakdown live;
``tools/trace_analyze.py`` is the offline CLI over the same code.
"""

from __future__ import annotations

import statistics
from typing import Dict, List, Optional

from .stage_spans import STAGES as ROUND_STAGES

# Server lanes start here in the merged file; worker lanes are the ranks.
SERVER_PID_BASE = 10000
# Device lanes (common/devprof.py step spans + parsed XLA events) start
# here — ABOVE the server band, so the lane bands are rank < 10000 <=
# server < 20000 <= device and `_is_server` must be bounded on both
# sides (an unbounded `pid >= SERVER_PID_BASE` would walk device spans
# as server work and corrupt the critical path).
DEVICE_PID_BASE = 20000
# Compile lanes (utils/compile_cache.py: the TRACE / LOWER / COMPILE spans
# of the process's compile log) start here, above the device band.
COMPILE_PID_BASE = 30000

WORKER_STAGES = ("QUEUE", "ENCODE", "PUSH", "PULL", "DECODE")
SERVER_STAGES = ("RECV", "SUM", "MERGE_WAIT", "PUBLISH", "PULL_SEND")
COMPONENTS = ("queue", "encode", "server_recv", "server_sum", "merge_wait",
              "push_wire", "pull_wire", "decode", "other")


def _is_server(e: dict) -> bool:
    pid = e.get("pid")
    return isinstance(pid, int) and SERVER_PID_BASE <= pid < DEVICE_PID_BASE


def _overlaps(e: dict, t0: int, t1: int) -> bool:
    return e["ts"] < t1 and e["ts"] + e.get("dur", 0) > t0


def _tensor_name(span_name: str) -> str:
    """Strip the ``.part<i>`` suffix: spans aggregate per tensor/bucket."""
    base, dot, tail = span_name.rpartition(".")
    if dot and tail.startswith("part") and tail[4:].isdigit():
        return base
    return span_name


def analyze(events: List[dict], worker: int = 0, top_k: int = 5) -> dict:
    """Analyze merged trace events; see the module docstring.

    ``worker`` selects whose chain is walked (server MERGE_WAIT/SUM spans
    are matched on ``args.worker``).  Returns a plain-dict report::

        {"steps": [{"name", "ts_us", "dur_us", "critical", "normalized",
                    "breakdown_us": {component: us}}],
         "mean_breakdown_us": {component: us},
         "top_blocking": [{"name", "total_us", "members"}],
         "straggler_wait_us": {worker_id: us},
         "round_breakdown_us": {"round", "pack", ..., "unspanned": us},
         "round_wire": {"send_calls", ..., "push_handoffs", "send_wall_us",
                        "handoff_wait_us", "lanes_sending", "lanes",
                        "lane_busy_us": [us]}}
    """
    xs = [e for e in events if e.get("ph") == "X"]
    # Worker-side spans and STEP envelopes are filtered to the selected
    # worker's pid: the CLI merges every worker's file, and without the
    # filter another worker's spans would overwrite this worker's chain
    # (and every worker's STEP envelopes would each produce a row).
    # Server spans stay un-filtered — all lanes serve all workers.
    steps = sorted((e for e in xs
                    if e.get("tid") == "STEP" and e.get("pid") == worker),
                   key=lambda e: e["ts"])
    wspans = [e for e in xs
              if e.get("pid") == worker and e.get("tid") in WORKER_STAGES
              and "args" in e]
    sspans = [e for e in xs if _is_server(e)]

    blocking: Dict[str, dict] = {}
    step_rows = []
    for st in steps:
        t0, t1 = st["ts"], st["ts"] + st.get("dur", 0)
        in_win = [e for e in wspans if _overlaps(e, t0, t1)]
        if not in_win:
            bd = {c: 0 for c in COMPONENTS}
            bd["other"] = t1 - t0
            step_rows.append({"name": st.get("name", "step"), "ts_us": t0,
                              "dur_us": t1 - t0, "critical": None,
                              "normalized": False, "breakdown_us": bd})
            continue
        # Group the window's worker spans by partition key; one stage may
        # repeat (several rounds of a key per step) — keep the LAST span,
        # which belongs to the chain that decides the step's tail.
        by_key: Dict[int, Dict[str, dict]] = {}
        for e in in_win:
            k = e["args"].get("key")
            if k is None:
                continue
            by_key.setdefault(k, {})[e["tid"]] = e
        if not by_key:
            continue

        def chain_end(stages: Dict[str, dict]) -> int:
            return max(e["ts"] + e.get("dur", 0) for e in stages.values())

        crit_key = max(by_key, key=lambda k: chain_end(by_key[k]))
        crit = by_key[crit_key]

        def wdur(stage: str) -> int:
            e = crit.get(stage)
            return int(e.get("dur", 0)) if e else 0

        def sdur(stage: str) -> int:
            # The matching server span: same key, inside the window,
            # attributed to our worker (MERGE_WAIT/SUM are per-pusher).
            best = 0
            for e in sspans:
                a = e.get("args") or {}
                if (e.get("tid") == stage and a.get("key") == crit_key
                        and a.get("worker") == worker
                        and _overlaps(e, t0, t1)):
                    best = max(best, int(e.get("dur", 0)))
            return best

        comp = {
            "queue": wdur("QUEUE"),
            "encode": wdur("ENCODE"),
            "server_recv": sdur("RECV"),
            "server_sum": sdur("SUM"),
            "merge_wait": sdur("MERGE_WAIT"),
            "decode": wdur("DECODE"),
        }
        # Wire components: worker-observed round trips minus the server
        # residency they contain.  PUSH ends at the server's merge ack
        # (RECV + SUM happen inside it); the straggler wait shows up in
        # PULL (the pull pends server-side until the round publishes).
        comp["push_wire"] = max(
            0, wdur("PUSH") - comp["server_recv"] - comp["server_sum"])
        comp["pull_wire"] = max(0, wdur("PULL") - comp["merge_wait"])
        step_dur = t1 - t0
        total = sum(comp.values())
        normalized = total > step_dur
        if normalized and total > 0:
            # Overlapping rounds inflated the chain past the envelope:
            # scale so the breakdown still partitions the step exactly.
            comp = {k: int(v * step_dur / total) for k, v in comp.items()}
            total = sum(comp.values())
        comp["other"] = step_dur - total
        crit_name = next((e.get("name") for s in ("PULL", "PUSH", "QUEUE")
                          for e in [crit.get(s)] if e), None)
        step_rows.append({"name": st.get("name", "step"), "ts_us": t0,
                          "dur_us": step_dur, "critical": crit_name,
                          "normalized": normalized, "breakdown_us": comp})

        # Blocking totals: how long each tensor's chain occupied the step
        # tail candidates (chain extent), plus fused-member attribution.
        for k, stages in by_key.items():
            ext = (chain_end(stages)
                   - min(e["ts"] for e in stages.values()))
            any_span = next(iter(stages.values()))
            nm = _tensor_name(any_span.get("name", f"key_{k}"))
            row = blocking.setdefault(nm, {"name": nm, "total_us": 0,
                                           "members": None})
            row["total_us"] += int(ext)
            members = (any_span.get("args") or {}).get("members")
            if members:
                row["members"] = list(members)

    # Straggler attribution from MERGE_WAIT: within one (key, round) the
    # LAST-merging worker (minimum wait) held the round open — every other
    # worker's wait is attributed to it.
    waits: Dict[tuple, List[dict]] = {}
    for e in sspans:
        if e.get("tid") != "MERGE_WAIT":
            continue
        a = e.get("args") or {}
        waits.setdefault((e.get("pid"), a.get("key"), a.get("round")),
                         []).append(e)
    straggler: Dict[int, int] = {}
    for group in waits.values():
        if len(group) < 2:
            continue
        last = min(group, key=lambda e: e.get("dur", 0))
        lw = (last.get("args") or {}).get("worker")
        attributed = sum(int(e.get("dur", 0)) for e in group
                         if e is not last)
        straggler[lw] = straggler.get(lw, 0) + attributed

    n = max(1, len(step_rows))
    mean = {c: sum(r["breakdown_us"][c] for r in step_rows) // n
            for c in COMPONENTS}
    top = sorted(blocking.values(), key=lambda r: -r["total_us"])[:top_k]
    return {"steps": step_rows, "mean_breakdown_us": mean,
            "top_blocking": top, "straggler_wait_us": straggler,
            "round_breakdown_us": round_breakdown(xs, worker),
            "round_wire": round_wire(xs, worker),
            "compile_log": compile_log(events, worker)}


def compile_log(events: List[dict], worker: int = 0, top_k: int = 5) -> dict:
    """What the file holds of the worker's compile log (its lane, pid
    ``COMPILE_PID_BASE + worker``; utils/compile_cache.py): the log's
    ``totals`` and ``steady_at_us`` as they stood when the file was
    written, and the ``longest`` of its ``TRACE`` / ``LOWER`` /
    ``COMPILE`` spans inside the traced window; empty for a file without
    the lane."""
    lane = [e for e in events if e.get("pid") == COMPILE_PID_BASE + worker]
    said = [e["args"] for e in lane if e.get("name") == "compile_log"]
    if not said:
        return {}
    spans = sorted((e for e in lane if e.get("ph") == "X"),
                   key=lambda e: -int(e.get("dur", 0)))
    return {"totals": said[0]["totals"],
            "steady_at_us": said[0]["steady_at_us"], "spans": len(spans),
            "longest": [{"kind": e["tid"], "name": e["name"],
                         "ts_us": e["ts"], "dur_us": e["dur"],
                         **e.get("args", {})} for e in spans[:top_k]]}


def round_breakdown(spans: List[dict], worker: int = 0) -> Dict[str, int]:
    """Mean microseconds per ``ROUND`` of the worker: ``round`` itself,
    each stage under it (lower-case) and ``unspanned``; empty where the
    events hold no ``ROUND``.  A stage span counts only if its
    ``args.round`` names one of those ``ROUND``s: the same stages are
    written with round 0 by callers that open none."""
    mine = [e for e in spans if e.get("pid") == worker
            and e.get("tid") in ROUND_STAGES]
    rounds = {e["args"]["round"] for e in mine if e["tid"] == "ROUND"}
    if not rounds:
        return {}
    total = dict.fromkeys(ROUND_STAGES, 0)
    for e in mine:
        if e["args"]["round"] in rounds:
            total[e["tid"]] += int(e.get("dur", 0))
    out = {s.lower(): total[s] // len(rounds) for s in ROUND_STAGES}
    out["unspanned"] = out["round"] - sum(
        v for s, v in out.items() if s != "round")
    return out


def round_wire(spans: List[dict], worker: int = 0) -> Dict[str, object]:
    """Mean per ``ROUND`` of what the session's lanes counted while it
    was open (its ``args``, but for the stage counts that
    ``round_breakdown`` and the byte counts cover); ``lane_busy_us`` a
    list, by lane.  ``lanes_sending``, where the program counts
    ``send_wall_us``: the rounds' ``send_us`` over it, the mean number
    of lanes inside a sending call while any was (1.0 for one sender,
    towards ``lanes`` for a sender a lane).  Empty where no ``ROUND``
    carries the counts."""
    args = [e["args"] for e in spans if e.get("pid") == worker
            and e.get("tid") == "ROUND" and "send_calls" in e["args"]]
    if not args:
        return {}
    n = len(args)
    out: Dict[str, object] = {
        k: sum(a[k] for a in args) // n for k in args[0]
        if k not in ("round", "units", "units_early", "bytes_out",
                     "bytes_in", "minflt", "lanes", "lane_busy_us")}
    wall = sum(a.get("send_wall_us", 0) for a in args)
    if wall:
        out["lanes_sending"] = round(
            sum(a["send_us"] for a in args) / wall, 3)
    out["lanes"] = max(a["lanes"] for a in args)
    out["lane_busy_us"] = [
        sum(a["lane_busy_us"][i] for a in args
            if i < len(a["lane_busy_us"])) // n
        for i in range(out["lanes"])]
    return out


def profiler_offset(events: List[dict], xplane_path: str,
                    worker: int = 0) -> Optional[Dict[str, float]]:
    """What to add to a ``comm.json`` time (microseconds) to put it on
    the clock of the ``jax.profiler`` trace at ``xplane_path``, taken
    from the rounds both hold: every ``ROUND`` span is also a
    ``byteps.round`` annotation carrying the same ``round``
    (common/stage_spans.py).  ``offset_us`` is the median over those
    rounds of annotation start less span start, ``spread_us`` the
    distance between the largest and smallest difference (what the
    alignment can be off by), ``rounds`` how many were in both.  None
    where no round is in both."""
    from jax.profiler import ProfileData
    spans = {e["args"]["round"]: e["ts"] for e in events
             if e.get("ph") == "X" and e.get("pid") == worker
             and e.get("tid") == "ROUND"}
    diffs = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name != "byteps.round":
                    continue
                rnd = dict(ev.stats).get("round")
                if rnd in spans:
                    diffs.append(ev.start_ns / 1e3 - spans[rnd])
    if not diffs:
        return None
    return {"offset_us": statistics.median(diffs),
            "spread_us": max(diffs) - min(diffs), "rounds": len(diffs)}


# Worker labels set by the previous update, per registry: the straggler
# label set varies window to window, and a gauge for a worker that has
# stopped straggling must drop to 0 rather than keep blaming it with the
# stale value ("the last analyzed trace window" means exactly that).
_prev_straggler_workers: "weakref.WeakKeyDictionary" = None  # built lazily


def update_critical_path_gauges(result: dict, registry=None) -> None:
    """Feed an ``analyze()`` result into the telemetry registry:
    ``bps_step_critical_path_seconds{component=…}`` (per-step mean) and
    ``bps_step_straggler_wait_seconds{worker=…}`` — live on the
    Prometheus endpoint and in ``tools/bps_top.py``."""
    global _prev_straggler_workers
    import weakref
    from . import telemetry
    if _prev_straggler_workers is None:
        _prev_straggler_workers = weakref.WeakKeyDictionary()
    reg = registry or telemetry.get_registry()
    for comp, us in result.get("mean_breakdown_us", {}).items():
        reg.gauge("bps_step_critical_path_seconds",
                  help="per-step mean critical-path time by component "
                       "(from the last analyzed trace window)",
                  labels={"component": comp}).set(us / 1e6)
    waits = {str(w): us for w, us in
             result.get("straggler_wait_us", {}).items()}
    stale = _prev_straggler_workers.get(reg, set()) - set(waits)
    for w in stale:
        waits[w] = 0
    for w, us in waits.items():
        reg.gauge("bps_step_straggler_wait_seconds",
                  help="merge-wait time other workers spent waiting on "
                       "this worker in the last analyzed trace window",
                  labels={"worker": w}).set(us / 1e6)
    _prev_straggler_workers[reg] = set(waits) - stale


def _fmt_us(us: float) -> str:
    if us >= 1e6:
        return f"{us / 1e6:8.2f}s "
    if us >= 1e3:
        return f"{us / 1e3:8.2f}ms"
    return f"{us:8.0f}us"


def format_report(result: dict) -> str:
    """Human-readable report (what ``tools/trace_analyze.py`` prints)."""
    lines = ["step critical path (per-step breakdown; sums to step time)"]
    for r in result.get("steps", []):
        bd = r["breakdown_us"]
        lines.append(f"  {r['name']:<12} {_fmt_us(r['dur_us'])} total"
                     + (f"   critical: {r['critical']}"
                        if r.get("critical") else "")
                     + ("   [normalized]" if r.get("normalized") else ""))
        for c in COMPONENTS:
            if bd.get(c):
                pct = 100.0 * bd[c] / max(1, r["dur_us"])
                lines.append(f"      {c:<12}{_fmt_us(bd[c])}  {pct:5.1f}%")
    mean = result.get("mean_breakdown_us", {})
    if mean:
        lines.append("mean per-step breakdown")
        for c in COMPONENTS:
            lines.append(f"      {c:<12}{_fmt_us(mean.get(c, 0))}")
    rounds = result.get("round_breakdown_us", {})
    if rounds:
        lines.append("mean per-round breakdown on the calling thread "
                     "(sums to the round)")
        for stage, us in rounds.items():
            lines.append(f"      {stage:<12}{_fmt_us(us)}")
    wire = result.get("round_wire", {})
    if wire:
        lines.append("what the wire waited for, mean per round (sums over "
                     "lanes and threads)")
        for key, value in wire.items():
            if key.endswith("_us") and key != "lane_busy_us":
                lines.append(f"      {key[:-3]:<22}{_fmt_us(value)}")
            elif key != "lane_busy_us":
                lines.append(f"      {key:<22}{value:>10}")
        lines.append("      lane busy             " + " ".join(
            _fmt_us(us).strip() for us in wire["lane_busy_us"]))
    made = result.get("compile_log", {})
    if made:
        totals = made["totals"]
        lines.append(
            "compile log of the process: "
            + ", ".join(f"{k.lower()} {v['seconds']:.2f}s in "
                        f"{v['records']} spans"
                        for k, v in totals["by_kind"].items())
            + "; programs " + " ".join(
                f"{k}={v}" for k, v in totals["by_cache"].items())
            + f"; recompiles {totals['recompiles']}")
        lines.append(f"      {made['spans']} of its spans inside the traced "
                     "window" + (", the longest:" if made["longest"] else ""))
        for row in made["longest"]:
            lines.append(
                f"      {row['kind']:<8}{_fmt_us(row['dur_us'])}  "
                f"{row['name']}" + "".join(
                    f"  {k}={row[k]}" for k in ("cache", "cause", "nested")
                    if row.get(k)))
    clock = result.get("profiler_offset")
    if clock:
        lines.append(f"comm.json + {clock['offset_us']:.1f}us = the "
                     f"profiler's clock (spread {clock['spread_us']:.2f}us "
                     f"over {clock['rounds']} rounds)")
    top = result.get("top_blocking", [])
    if top:
        lines.append("top blocking tensors (chain extent, all steps)")
        for row in top:
            lines.append(f"  {_fmt_us(row['total_us'])}  {row['name']}")
            if row.get("members"):
                lines.append("      members: " + ", ".join(row["members"]))
    stragglers = result.get("straggler_wait_us", {})
    if stragglers:
        lines.append("straggler attribution (merge-wait caused, by worker)")
        for w, us in sorted(stragglers.items(), key=lambda kv: -kv[1]):
            lines.append(f"  worker {w}: {_fmt_us(us)} of peer wait")
    return "\n".join(lines)
