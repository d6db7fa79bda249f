"""Main-thread stage spans of a PS round (docs/timeline.md, "The round
from inside").

The dispatcher threads span every partition (QUEUE / PUSH / PULL ...);
what the calling thread does around them had no span.  This is the one
helper those sites use: a span goes to the core tracer like every other
(``core.trace_record_args``; gated by ``core.trace_on``, dumped into
``comm.json``) and, for its extent, enters
``jax.profiler.TraceAnnotation("byteps.<stage>")``, which is inert with
no profiler session and otherwise lands in the ``/host:CPU`` plane of
the ``.xplane.pb``.  A ``ROUND`` is therefore in both records, and the
difference of its two start times is the offset between the tracer's
clock and the profiler's.

``round`` in a span's args is the session's count of ``ROUND``s, the
identifier a child repeats to name its cause; it is 0 for a span written
with no ``ROUND`` open on its thread (``push_pull_async`` alone, the
async and server-side trainers).  It is not the wire protocol's per-key
round (``args.round`` of the server's spans).
"""

from __future__ import annotations

import contextlib
import resource
import threading

from ..core.native import get_core

# In the order a round passes through them.  Disjoint from
# trace_analysis.WORKER_STAGES: the per-partition readers skip these.
STAGES = ("ROUND", "PACK", "D2H", "STAGE", "WAIT", "H2D", "SCATTER", "FREE")

_OFF = contextlib.nullcontext()


def off(*_args, **_kwargs):
    """`RoundSpans.span` for a caller with no PS session: no span."""
    return _OFF


class _Span:
    """One open stage span; `args` may be added to until it closes."""

    __slots__ = ("_core", "stage", "name", "args", "_t0", "_annotation")

    def __init__(self, core, stage: str, name: str, args: dict):
        self._core, self.stage, self.name, self.args = core, stage, name, args

    def __enter__(self):
        from jax.profiler import TraceAnnotation
        self._annotation = TraceAnnotation("byteps." + self.stage.lower(),
                                           round=self.args["round"])
        self._annotation.__enter__()
        self._t0 = self._core.trace_now_us()
        return self

    def __exit__(self, *exc):
        dur = self._core.trace_now_us() - self._t0
        self._annotation.__exit__(*exc)
        self._core.trace_record_args(self.name, self.stage, self._t0, dur,
                                     self.args)
        return False


def _minor_faults() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_minflt


def _wire_delta(before: dict, after: dict) -> dict:
    """What the lanes counted between two `PSSession.wire_counts`."""
    busy, was = after["lane_busy_us"], before["lane_busy_us"]
    if len(was) == len(busy):     # else the pool was resized in between
        busy = [b - a for a, b in zip(was, busy)]
    delta = {k: v - before[k] for k, v in after.items()
             if k != "lane_busy_us"}
    return {**delta, "lanes": len(busy), "lane_busy_us": busy}


class RoundSpans:
    """A PS session's round counter, the round open on each thread, and
    the spans written under it."""

    def __init__(self, wire=None):
        self._rounds = 0
        self._lock = threading.Lock()
        self._open = threading.local()    # .counts: the open ROUND's args
        # `PSSession.wire_counts`: the lanes' lifetime counters, which a
        # ROUND carries the deltas of.
        self._wire = wire
        self.last = None    # the args of the last ROUND that closed

    @contextlib.contextmanager
    def round(self, name: str):
        """The `ROUND` span around one `push_pull_tree` call.  Its args
        are the round's number, what `count` adds up inside it, and
        `minflt`: the minor page faults the process took while it was
        open, on every thread (the dispatcher's `recv_into` first
        touches the result buffers).  Near the round's bytes in pages
        where its host memory is new, near nothing where it was kept
        (common/host_memory.py).  Taken where `minflt` is, because the
        dispatcher and the receivers cannot reach this thread's counts:
        what the session's lanes counted while it was open
        (`PSSession.WIRE_COUNTS`), and `lanes` / `lane_busy_us`, each
        lane's time with bytes outstanding."""
        core = get_core()
        if not core.trace_on:
            yield
            return
        with self._lock:
            self._rounds += 1
            counts = {"round": self._rounds, "units": 0, "units_early": 0,
                      "bytes_out": 0, "bytes_in": 0}
        self._open.counts = counts
        try:
            with _Span(core, "ROUND", name, counts):
                faults = _minor_faults()
                wire = self._wire() if self._wire is not None else None
                try:
                    yield
                finally:
                    counts["minflt"] = _minor_faults() - faults
                    if wire is not None:
                        counts.update(_wire_delta(wire, self._wire()))
        finally:
            self._open.counts = None
            self.last = counts

    def span(self, stage: str, name: str, **args):
        """A context manager for one stage span, yielding the span (add
        to its `args` what is known only inside) or, with tracing off,
        None."""
        core = get_core()
        if not core.trace_on:
            return _OFF
        counts = getattr(self._open, "counts", None)
        return _Span(core, stage, name,
                     {"round": counts["round"] if counts else 0, **args})

    def count(self, **add) -> None:
        """Add to the counts of the `ROUND` open on this thread."""
        counts = getattr(self._open, "counts", None)
        if counts is not None:
            for k, v in add.items():
                counts[k] += v
