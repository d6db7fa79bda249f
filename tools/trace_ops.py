"""Where a traced step's time goes, instruction by instruction:

    python3 tools/trace_ops.py <dir or .xplane.pb> [--top 40] [--chars 200]

Reads a `jax.profiler` trace with the benchmark's own reader
(`benchmark/reduce/xplane.py`) and prints, for chip 0, the instructions
that took most time of their own (a `while`'s time without its body's),
summed over instructions of one name and shape, with the start of each
one's text: enough to see what a new part of a step is called in the
device trace before a reader is written against it.
"""

import argparse
import collections
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--top", type=int, default=40)
    ap.add_argument("--chars", type=int, default=200)
    args = ap.parse_args(argv)
    from benchmark.reduce import intervals, xplane
    path = args.trace if args.trace.endswith(".pb") else xplane.find(
        args.trace)
    ops = xplane.read(path).ops[0]
    total = collections.Counter()
    calls = collections.Counter()
    for name, own in intervals.self_times(ops).items():
        key = re.sub(r"\.\d+ = ", " = ", name[:args.chars], count=1)
        total[key] += own
        calls[key] += 1
    busy = intervals.total(intervals.union((s, e) for _, s, e in ops))
    print(json.dumps({"busy_ms": busy / 1e6, "instructions": len(ops)}))
    for key, own in total.most_common(args.top):
        print(f"{own / 1e6:10.3f} ms  {100 * own / busy:5.1f}%  "
              f"x{calls[key]:<4d} {key}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
