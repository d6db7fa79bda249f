"""PS staging: minor page faults the process took inside a `ROUND`, on
every thread, mean of the traced rounds: `args.minflt` of the program's
`ROUND` spans.  Near the round's host bytes in pages where every round
writes into memory the kernel has just handed out, near nothing where
the process kept it.  A program whose `ROUND` has no such count reads
nothing.  Source: program counter."""

import json
import os


def read(ctx):
    path = os.path.join(ctx.dir, "0", "comm.json")
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    faults = [e["args"]["minflt"] for e in events
              if e.get("ph") == "X" and e.get("pid") == 0
              and e.get("tid") == "ROUND" and "minflt" in e["args"]]
    return sum(faults) / len(faults) if faults else None
