#!/usr/bin/env python
"""check_test_budget — what the tier-1 suite may cost, judged by name.

The driver runs the whole non-slow suite under one hard timeout, on six
workers that are handed whole files (`DRIVER_COMMAND` below).  A run the
clock cuts is the worst failure there is: it counts only as far as it
got, writes no junit file and names no culprit.  Three things cut a run,
and this gate fails on each BY NAME, from one recording:

- a single non-``slow`` test whose call phase exceeds ``--budget``
  seconds (default 60);
- a FILE whose non-slow tests sum to more than ``FILE_BUDGET_S`` (350):
  `--dist loadfile` hands out whole files in collection order, so the
  wall time is at worst the total's sixth plus five sixths of the longest
  file;
- the TOTAL: its sixth (the wall time if the files fell evenly) over
  ``TOTAL_SHARE`` (80%) of the driver's limit.

Data source, in order of preference:

1. ``tests/.last_durations.json`` — written by the conftest recorder at
   every pytest session end: the complete ``pytest --durations`` data
   (call-phase seconds + slow-marker flag per nodeid), machine-readable
   and untruncated.
2. ``--log FILE`` — a pytest output log produced WITH ``--durations=0``;
   the classic ``12.34s call path::test`` rows are parsed instead
   (slow-marker information is absent there, so pass ``--log`` only for
   runs that already deselected slow tests, e.g. the tier-1 command).

Wired as a fast tier-1 test (tests/test_test_budget.py) over the
PREVIOUS run's recording — a breach lands on the next run, which is
exactly when a reviewer is still looking at the PR that caused it.
Also runnable standalone:

    python tools/check_test_budget.py [--budget 60] [--json]
    python tools/check_test_budget.py --log /tmp/_t1.log

Exit codes: 0 = within budget (or no data yet), 1 = budget exceeded,
2 = usage error.  ``BYTEPS_TPU_TEST_BUDGET_S`` overrides the default
per-test budget (documented in docs/env.md).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Dict, Optional

DEFAULT_BUDGET_S = 60.0
#: The driver's limit and its workers, from the command it runs
#: (`commands` in /root/TESTS_LAST_RUN.json; ROADMAP.md "Tier-1 verify"):
DRIVER_COMMAND = ("timeout -k 10 1470 env JAX_PLATFORMS=cpu ... python -m "
                  "pytest tests/ -q -m 'not slow' ... -p xdist -n 6 "
                  "--dist loadfile")
DRIVER_TIMEOUT_S = 1470.0
DRIVER_WORKERS = 6
#: What a file's non-slow tests may sum to, and the share of the driver's
#: limit that the total's sixth may take.
FILE_BUDGET_S = 350.0
TOTAL_SHARE = 0.8

#: pytest --durations row: "  12.34s call     tests/test_x.py::test_y"
_DURATION_ROW = re.compile(
    r"^\s*(\d+(?:\.\d+)?)s\s+(call|setup|teardown)\s+(\S+)\s*$")


def default_data_path() -> str:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(root, "tests", ".last_durations.json")


def load_recorded(path: str) -> Optional[Dict[str, dict]]:
    """The conftest recorder's {nodeid: {"duration", "slow"}} map, or
    None when no recording exists yet (first run / clean checkout)."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except OSError:
        return None
    except ValueError:
        print(f"check_test_budget: unreadable recording {path}; "
              f"treating as no data", file=sys.stderr)
        return None
    d = doc.get("durations")
    return d if isinstance(d, dict) else None


def parse_durations_log(text: str) -> Dict[str, dict]:
    """{nodeid: {"duration", "slow": False}} from a pytest log produced
    with ``--durations=0`` — call-phase rows only (setup/teardown waits
    are fixture costs, budgeted with the test that pays them in the
    recorder path but unattributable here)."""
    out: Dict[str, dict] = {}
    for line in text.splitlines():
        m = _DURATION_ROW.match(line)
        if m and m.group(2) == "call":
            nodeid = m.group(3)
            dur = float(m.group(1))
            if dur > out.get(nodeid, {}).get("duration", -1.0):
                out[nodeid] = {"duration": dur, "slow": False}
    return out


def check(durations: Dict[str, dict],
          budget_s: float = DEFAULT_BUDGET_S) -> dict:
    """The gate as a pure function (the self-test's entry point):
    non-slow tests over budget, slowest first; files whose non-slow
    tests sum to more than `FILE_BUDGET_S`, longest first; and the
    non-slow total against what the driver's limit leaves it."""
    offenders = []
    slow_exempt = 0
    by_file: Dict[str, float] = {}
    for nodeid, rec in durations.items():
        dur = float(rec.get("duration", 0.0))
        if rec.get("slow"):
            slow_exempt += 1
            continue
        path = nodeid.split("::", 1)[0]
        by_file[path] = by_file.get(path, 0.0) + dur
        if dur > budget_s:
            offenders.append({"nodeid": nodeid,
                              "duration": round(dur, 3)})
    offenders.sort(key=lambda r: -r["duration"])
    files = sorted(({"file": f, "duration": round(d, 3)}
                    for f, d in by_file.items() if d > FILE_BUDGET_S),
                   key=lambda r: -r["duration"])
    total = sum(by_file.values())
    return {"budget_s": budget_s, "tests": len(durations),
            "slow_exempt": slow_exempt, "offenders": offenders,
            "file_budget_s": FILE_BUDGET_S, "files_over": files,
            "total_s": round(total, 3),
            "total_budget_s": TOTAL_SHARE * DRIVER_TIMEOUT_S
            * DRIVER_WORKERS,
            "total_over": total / DRIVER_WORKERS
            > TOTAL_SHARE * DRIVER_TIMEOUT_S}


def over(report: dict) -> bool:
    return bool(report["offenders"] or report["files_over"]
                or report["total_over"])


def render(report: dict) -> str:
    lines = [f"check_test_budget: {report['tests']} test(s), budget "
             f"{report['budget_s']:g}s per non-slow test "
             f"({report['slow_exempt']} slow-marked exempt), "
             f"{report['file_budget_s']:g}s per file, "
             f"{report['total_budget_s']:g}s in all"]
    for o in report["offenders"]:
        lines.append(f"  {o['duration']:8.1f}s  {o['nodeid']}  "
                     f"<-- OVER BUDGET (mark it slow, split it, or "
                     f"speed it up)")
    for o in report["files_over"]:
        lines.append(f"  {o['duration']:8.1f}s  {o['file']}  "
                     f"<-- FILE OVER BUDGET (one worker runs a whole "
                     f"file: split it, or speed its tests up)")
    if report["total_over"]:
        lines.append(
            f"  {report['total_s']:8.1f}s  in all, "
            f"{report['total_s'] / DRIVER_WORKERS:.0f}s a worker of "
            f"{DRIVER_WORKERS}  <-- TOTAL OVER {TOTAL_SHARE:.0%} of the "
            f"driver's {DRIVER_TIMEOUT_S:g}s")
    if over(report):
        lines.append(f"{len(report['offenders'])} test(s), "
                     f"{len(report['files_over'])} file(s) over budget, "
                     f"total {report['total_s']:.0f}s"
                     + " over budget" * report["total_over"])
    else:
        lines.append(f"all within budget (total {report['total_s']:.0f}s)")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", nargs="?", default=default_data_path(),
                    help="durations recording (default: "
                         "tests/.last_durations.json)")
    ap.add_argument("--log", default=None,
                    help="parse a pytest --durations=0 output log "
                         "instead of the recording")
    ap.add_argument("--budget", type=float,
                    default=float(os.environ.get(
                        "BYTEPS_TPU_TEST_BUDGET_S") or DEFAULT_BUDGET_S),
                    help="per-test seconds allowed (default 60; env "
                         "BYTEPS_TPU_TEST_BUDGET_S overrides)")
    ap.add_argument("--json", action="store_true",
                    help="emit the report as one JSON object")
    args = ap.parse_args(argv)
    if args.budget <= 0:
        print("check_test_budget: --budget must be > 0", file=sys.stderr)
        return 2
    if args.log:
        try:
            with open(args.log) as f:
                durations = parse_durations_log(f.read())
        except OSError as e:
            print(f"check_test_budget: cannot read {args.log}: {e}",
                  file=sys.stderr)
            return 2
    else:
        durations = load_recorded(args.path)
        if durations is None:
            print("check_test_budget: no durations recorded yet "
                  f"({args.path}) — nothing to check")
            return 0
    report = check(durations, budget_s=args.budget)
    if args.json:
        print(json.dumps(report))
    else:
        print(render(report))
    return 1 if over(report) else 0


if __name__ == "__main__":
    sys.exit(main())
