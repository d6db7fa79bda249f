"""The profiler's `.xplane.pb` reduced to intervals.

What a trace of this runtime holds (looked at by hand on a TPU v5e,
PERF.md Findings, PR 24): one plane `/device:TPU:<n>` per chip with the
lines `XLA Modules` (one event per program run), `XLA Ops` (one event per
HLO instruction run, nested: a `while`, `call` or `conditional` holds the
instructions of its body), `Async XLA Ops` (one event from every
`*-start` to its `*-done`: copies and, across chips, collectives) and
`Steps`; and a plane `/host:CPU` whose lines are host threads, where
`jax.profiler.TraceAnnotation`s appear under their own names.  An op
event's name is the whole text of its HLO instruction,
`%fusion.371 = bf16[...]{...} fusion(...), kind=kLoop, ...`; events carry
no category, FLOP count or source operation.  All times are nanoseconds
on one clock for host and devices.

Read with `jax.profiler.ProfileData`, which needs nothing but JAX.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_HOST_PLANE = "/host:CPU"
_OPCODE = re.compile(r"(?<![\w.\-])([a-z][a-z0-9\-]*)\(")
# Opcodes that move data between chips.  An asynchronous one shows as a
# `-start` and a `-done` instruction on `XLA Ops` (each short) and as one
# span from start to done on `Async XLA Ops`.
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast")


def op_name(instruction: str) -> str:
    """`%fusion.371 = ...` -> `fusion.371`."""
    return instruction.split(" = ", 1)[0].lstrip("%")


def opcode(instruction: str) -> str:
    """The HLO opcode of an instruction's text: the first lower-case word
    before a `(` after the result shape (shapes use `[`, `{` and the
    upper-case `T(`, `S(` of tilings)."""
    m = _OPCODE.search(instruction.split(" = ", 1)[-1])
    return m.group(1) if m else ""


def is_collective(instruction: str) -> bool:
    code = opcode(instruction)
    return any(code == c or code in (c + "-start", c + "-done")
               for c in COLLECTIVES)


@dataclasses.dataclass
class Trace:
    """Events as `(name, start_ns, end_ns)`.  `ops` and `async_ops` are
    per chip, in the order of the chips' plane numbers."""
    ops: list           # line `XLA Ops`
    async_ops: list     # line `Async XLA Ops`
    host: list          # events of every host thread


def find(trace_dir: str):
    """The one `.xplane.pb` under a directory `jax.profiler` wrote to."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def read(path: str, host_prefix: str = "") -> Trace:
    """`host_prefix` keeps only the host events whose name starts with
    it: a host plane also holds the runtime's own spans by the
    thousand."""
    from jax.profiler import ProfileData
    chips: dict = {}
    host = []
    for plane in ProfileData.from_file(path).planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            chips[int(m.group(1))] = {
                line.name: [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
                for line in plane.lines
                if line.name in ("XLA Ops", "Async XLA Ops")}
        elif plane.name == _HOST_PLANE:
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if e.name.startswith(host_prefix))
    order = sorted(chips)
    return Trace(ops=[chips[c].get("XLA Ops", []) for c in order],
                 async_ops=[chips[c].get("Async XLA Ops", []) for c in order],
                 host=host)


def leaves(events) -> list:
    """The events that hold no other event: the instructions that run,
    without the `while`s and `call`s around them."""
    out = []
    ordered = sorted(events, key=lambda e: (e[1], -e[2]))
    for i, e in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is None or nxt[1] >= e[2]:
            out.append(e)
    return out
