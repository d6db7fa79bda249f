#!/usr/bin/env python
"""trace_analyze — critical-path breakdown of a merged byteps_tpu trace.

Reads one or more merged ``comm.json`` files (worker spans + server spans
on one aligned clock, see docs/timeline.md) and prints, per step: the
critical partition chain and a queue / encode / wire / server merge-wait /
sum / decode breakdown that sums to the measured step time — plus top-k
blocking tensors (with fused-bucket member attribution) and per-worker
straggler attribution from the server MERGE_WAIT spans, and the totals
of the process's compile log with the five longest of its TRACE / LOWER /
COMPILE spans inside the traced window.

Usage:
    python tools/trace_analyze.py traces/0/comm.json
    python tools/trace_analyze.py traces/*/comm.json --worker 0 --top 10
    python tools/trace_analyze.py traces/0/comm.json --json
    python tools/trace_analyze.py traces/0/comm.json --xplane t.xplane.pb

Multiple files merge before analysis: in a multi-worker run each server
span is drained by exactly one worker, so pass every worker's file to see
the whole fleet.  With ``--xplane`` (a ``jax.profiler`` trace of the
same run) it also gives the offset that puts comm.json on the profiler's
clock, from the rounds both record.  No dependencies beyond the stdlib +
byteps_tpu.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

from byteps_tpu.common import trace_analysis  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("files", nargs="+", help="merged comm.json file(s)")
    ap.add_argument("--worker", type=int, default=0,
                    help="whose chain to walk (default rank 0)")
    ap.add_argument("--top", type=int, default=5,
                    help="top-k blocking tensors (default 5)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable result instead of the report")
    ap.add_argument("--xplane", metavar="PATH",
                    help=".xplane.pb of the same run: also print the "
                         "offset from comm.json's clock to the profiler's")
    args = ap.parse_args(argv)

    events = []
    for path in args.files:
        with open(path) as f:
            doc = json.load(f)
        events.extend(doc.get("traceEvents", []))
    if not events:
        print("no trace events found", file=sys.stderr)
        return 1
    result = trace_analysis.analyze(events, worker=args.worker,
                                    top_k=args.top)
    if args.xplane:
        result["profiler_offset"] = trace_analysis.profiler_offset(
            events, args.xplane, worker=args.worker)
    if args.json:
        print(json.dumps(result, indent=2))
    else:
        print(trace_analysis.format_report(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
