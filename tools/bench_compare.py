#!/usr/bin/env python
"""bench_compare — regression gate over the BENCH_* / MULTICHIP_* record
series.

Reads a directory of ``BENCH_rNN.json`` / ``MULTICHIP_rNN.json`` records
(bench.py wrapper shape: ``{"n", "cmd", "rc", "parsed": {"metric",
"value", "unit", "detail": {...}}}``).  A silent regression rides such a
history when a CPU-fallback number that *reads* like an on-chip one
becomes the implicit baseline.  bench.py's device modes no longer run on
the CPU at all; this tool closes the read side for records that exist:

  For the LATEST record of each (headline metric, device platform)
  pair, compare against the BEST prior non-fallback record of the same
  pair and flag any regression worse than ``--threshold`` (default
  10%).

Fallback records (``"fallback": true`` stamp, ``cpu_fallback_*`` unit,
or a ``cpu-fallback`` provenance note) are never used as baselines, and
platform pairing means a fallback candidate is only ever judged against
other explicit-CPU numbers — apples to apples by construction.
Direction is inferred from the metric: ``*_ms`` / second-ish units are
lower-is-better, everything else higher-is-better.

    python tools/bench_compare.py [root] [--json] [--threshold 0.10]

Exit codes: 0 = no regression (or nothing comparable), 3 = regression
flagged (bench.py's refusal convention), 1 = usage error.  Wired as a
self-tested fast tier-1 test (tests/test_bench_compare.py) on synthetic
records, so the gate itself can't silently rot.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import List, Optional

_SEQ_RE = re.compile(r"_r(\d+)\.json$")

DEFAULT_THRESHOLD = 0.10


def _is_fallback(parsed: dict) -> bool:
    detail = parsed.get("detail") or {}
    if detail.get("fallback") or detail.get("device_fallback"):
        return True
    if str(parsed.get("unit", "")).startswith("cpu_fallback_"):
        return True
    note = str(detail.get("note", ""))
    return "cpu-fallback" in note or "cpu fallback" in note


def _platform(parsed: dict) -> str:
    detail = parsed.get("detail") or {}
    p = detail.get("device_platform")
    if p:
        return str(p)
    # Pre-stamp records: infer from the fallback note, else unknown.
    return "cpu" if _is_fallback(parsed) else "unknown"


# Throughput-ish shapes that are HIGHER-is-better and must be
# recognized explicitly: a rate metric named "*_per_s" / "*_rows_s"
# would otherwise match the "_s" time suffix below and read as
# lower-is-better — a goodput IMPROVEMENT would then flag as a
# regression.  Checked before the time-suffix rules for exactly that
# reason.
_HIGHER_METRIC_SUFFIXES = (
    "_mbps", "_gbps", "_mb_s", "_gb_s", "_goodput", "_throughput",
    "_per_s", "_per_sec", "_rows_s", "_tokens_s", "_items_s", "_qps",
    "_mfu", "_efficiency", "_pct_of_floor", "_pct_of_peak", "_saved_pct",
    "_hit_rate",
    # BENCH_FLEET's goodput-ledger headline: a percentage where more
    # compute share is better — named explicitly so it never drifts
    # onto a lower-is-better *_pct rule (the _gap_pct family below).
    "_goodput_pct",
)
_HIGHER_UNITS = {
    "mbps", "gbps", "mb/s", "gb/s", "mb_s", "gb_s", "goodput_mbps",
    "per_s", "per_sec", "qps", "rows_s", "rows_per_s", "tokens_s",
    "items_per_s", "steps_per_s", "pct_of_floor", "pct_of_peak", "mfu",
    "ratio", "x",
}

# Percentile-tail names (BENCH_SPARSE p99 pull latency and friends):
# a pNN_ prefix marks a latency-distribution tail, lower-is-better
# whatever the suffix spells — checked after the explicit-higher rules
# so a hypothetical "p99_*_hit_rate" still reads as a rate.
_PCTL_PREFIXES = ("p50_", "p90_", "p95_", "p99_", "p999_")


def _lower_is_better(metric: str, unit: str) -> bool:
    unit = unit[len("cpu_fallback_"):] if unit.startswith(
        "cpu_fallback_") else unit
    # Explicit higher-is-better first: throughput/goodput/efficiency
    # shapes, including rate names that also end in "_s".
    if metric.endswith(_HIGHER_METRIC_SUFFIXES) \
            or unit.lower() in _HIGHER_UNITS:
        return False
    if metric.startswith(_PCTL_PREFIXES):
        return True
    if metric.endswith(("_ms", "_ns", "_s", "_seconds", "_latency")):
        return True
    # The gap family (BENCH_AUTOTUNE / BENCH_SERVEROPT / BENCH_KNOB):
    # the headline is the step-time GAP between the adaptive run and
    # its hand-tuned/baseline config — a percentage where smaller means
    # more of the gap closed (0 = converged, negative = outright
    # faster).  Without this, "pct" would read as higher-is-better and
    # a converging tuner would flag as a regression.
    if metric.endswith("_gap_pct") or unit == "pct_gap":
        return True
    # The robustness families (BENCH_ELASTIC with replication armed):
    # lost rounds on a failover, how far replication trails the publish
    # cursor, and how long the autoscaler took to notice pressure — all
    # counts where 0 is the law and any growth is a regression.  A bare
    # "_rounds" suffix would otherwise fall through to higher-is-better
    # (completed_round-style progress counters legitimately read that
    # way), so the loss/lag shapes are named explicitly
    # (autoscale_detect_ms already reads lower via the _ms rule above).
    if metric.endswith(("_lost_rounds", "_lag_rounds", "_overhead_pct")):
        return True
    return unit in ("ms", "ns", "s", "seconds", "us")


def load_records(root: str) -> List[dict]:
    """Flat record list from BENCH_*.json / MULTICHIP_*.json files.
    Unparseable files are skipped with a warning — one corrupt record
    must not hide the rest of the series."""
    out: List[dict] = []
    for pattern in ("BENCH_*.json", "MULTICHIP_*.json"):
        for path in sorted(glob.glob(os.path.join(root, pattern))):
            m = _SEQ_RE.search(os.path.basename(path))
            seq = int(m.group(1)) if m else -1
            try:
                with open(path) as f:
                    doc = json.load(f)
            except (OSError, ValueError) as e:
                print(f"bench_compare: skipping {path}: {e}",
                      file=sys.stderr)
                continue
            parsed = doc.get("parsed") if isinstance(doc, dict) else None
            if not parsed and isinstance(doc, dict) and "metric" in doc:
                parsed = doc              # raw bench.py output shape
            if parsed and "metric" in parsed and isinstance(
                    parsed.get("value"), (int, float)):
                out.append({
                    "file": os.path.basename(path),
                    "seq": seq if seq >= 0 else int(doc.get("n", -1)),
                    "metric": str(parsed["metric"]),
                    "value": float(parsed["value"]),
                    "unit": str(parsed.get("unit", "")),
                    "platform": _platform(parsed),
                    "fallback": _is_fallback(parsed),
                })
            elif isinstance(doc, dict) and "ok" in doc:
                # MULTICHIP dryrun records: {"n_devices", "rc", "ok"} —
                # gate ok=true -> false regressions (a broken multichip
                # path is a 100% regression of its one headline bit).
                out.append({
                    "file": os.path.basename(path),
                    "seq": seq,
                    "metric": "multichip_dryrun_ok",
                    "value": 1.0 if doc.get("ok") else 0.0,
                    "unit": "bool",
                    "platform": "dryrun",
                    "fallback": False,
                })
    return out


def check(records: List[dict],
          threshold: float = DEFAULT_THRESHOLD) -> dict:
    """The gate, as a pure function over record dicts (the self-test's
    entry point).  Returns {"groups": [...], "regressions": [...]}."""
    groups: dict = {}
    for r in records:
        groups.setdefault((r["metric"], r["platform"]), []).append(r)
    rows, regressions = [], []
    for (metric, platform), recs in sorted(groups.items()):
        recs = sorted(recs, key=lambda r: r["seq"])
        latest = recs[-1]
        lower = _lower_is_better(metric, latest["unit"])
        prior = [r for r in recs[:-1] if not r["fallback"]]
        row = {"metric": metric, "platform": platform,
               "latest": latest["value"], "latest_file": latest["file"],
               "latest_fallback": latest["fallback"],
               "direction": "lower" if lower else "higher",
               "records": len(recs)}
        if not prior:
            row.update(status="no-baseline", baseline=None)
            rows.append(row)
            continue
        best = (min if lower else max)(prior, key=lambda r: r["value"])
        base = best["value"]
        if base == 0:
            change = 0.0 if latest["value"] == 0 else 1.0
        elif lower:
            change = (latest["value"] - base) / abs(base)
        else:
            change = (base - latest["value"]) / abs(base)
        row.update(baseline=base, baseline_file=best["file"],
                   regression_frac=round(change, 4))
        if change > threshold:
            row["status"] = "REGRESSED"
            regressions.append(row)
        else:
            row["status"] = "ok"
        rows.append(row)
    return {"threshold": threshold, "groups": rows,
            "regressions": regressions}


def render(report: dict) -> str:
    lines = [f"bench_compare: {len(report['groups'])} (metric, "
             f"platform) group(s), threshold "
             f"{report['threshold']:.0%}"]
    for row in report["groups"]:
        if row["status"] == "no-baseline":
            detail = "no prior non-fallback baseline"
        else:
            detail = (f"latest {row['latest']:g} vs best "
                      f"{row['baseline']:g} ({row['baseline_file']}), "
                      f"{row['regression_frac']:+.1%} "
                      f"({row['direction']}-is-better)")
        tag = " <-- REGRESSED" if row["status"] == "REGRESSED" else ""
        fb = " [fallback]" if row.get("latest_fallback") else ""
        lines.append(f"  {row['metric']} @{row['platform']}{fb}: "
                     f"{detail}{tag}")
    if report["regressions"]:
        lines.append(f"{len(report['regressions'])} metric(s) regressed "
                     f"> {report['threshold']:.0%} vs the best prior "
                     f"non-fallback record")
    else:
        lines.append("no regressions")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("root", nargs="?",
                    default=os.path.dirname(os.path.dirname(
                        os.path.abspath(__file__))),
                    help="directory holding BENCH_*.json / "
                         "MULTICHIP_*.json (default: repo root)")
    ap.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD,
                    help="regression fraction to flag (default 0.10)")
    ap.add_argument("--json", action="store_true",
                    help="emit the report as one JSON object")
    args = ap.parse_args(argv)
    records = load_records(args.root)
    report = check(records, threshold=args.threshold)
    if args.json:
        print(json.dumps(report))
    else:
        print(render(report))
    return 3 if report["regressions"] else 0


if __name__ == "__main__":
    sys.exit(main())
