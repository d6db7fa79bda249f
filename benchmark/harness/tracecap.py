"""Capture of a profiler trace around a few steady steps, and the context
the per-layer readers read from."""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import shutil

import jax

from benchmark.reduce import intervals, xplane

WINDOW = "bench.window"      # the annotation around the traced steps
PREFIX = "bench."            # every annotation the benchmark makes


@dataclasses.dataclass(frozen=True)
class TraceWindow:
    """Which steps of a job are traced, and where its files go."""
    dir: str
    first_step: int
    n_steps: int

    @property
    def last_step(self) -> int:
        return self.first_step + self.n_steps - 1


@contextlib.contextmanager
def capture(trace_dir: str):
    """`jax.profiler` on for the body, into an emptied `trace_dir`.  The
    Python tracer is off: it slows the host it measures and the readers
    use no Python frame."""
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            yield
    finally:
        jax.profiler.stop_trace()


@dataclasses.dataclass(frozen=True)
class Context:
    """What a reader of a per-layer metric may read."""
    trace: xplane.Trace
    n_steps: int
    n_chips: int
    samples_per_step: int
    family: object
    peaks: dict
    extras: dict        # the job's own, such as its round timers
    first_step: int     # steps the job had made before the window
    dir: str            # where the trace, and the job's own files, went

    @functools.cached_property
    def window(self):
        """The traced window on the devices' clock, first instruction to
        last over all chips, or None.  The profiler is started after the
        last warm-up step has been waited for and stopped after the last
        traced loss has arrived, so every instruction in the trace
        belongs to the traced steps.  The host's annotations are not used
        for this: on this runtime the host's clock and a chip's differ
        by about a millisecond (a program starts, by the trace, 1.2 ms
        before the call that dispatches it), which also blurs
        `breakdown`'s attribution of idle time by that much."""
        spans = [(s, e) for ops in self.trace.ops for _, s, e in ops]
        if not spans:
            return None
        return min(s for s, _ in spans), max(e for _, e in spans)

    def ops(self, chip: int = 0) -> list:
        """Instruction events of a chip."""
        return self.trace.ops[chip] if chip < len(self.trace.ops) else []

    def async_ops(self, chip: int = 0) -> list:
        return (self.trace.async_ops[chip]
                if chip < len(self.trace.async_ops) else [])

    def busy(self, chip: int = 0) -> list:
        """Disjoint intervals in which an instruction ran on the chip."""
        return intervals.union((s, e) for _, s, e in self.ops(chip))

    def collectives(self, chip: int = 0) -> list:
        """`(start, end)` of every exchange between chips: the
        collective instructions themselves and, for asynchronous ones,
        the span from `-start` to `-done`."""
        return [(s, e) for n, s, e in self.ops(chip) + self.async_ops(chip)
                if xplane.is_collective(n)]

    def compute(self, chip: int = 0) -> list:
        """`(start, end)` of every instruction that runs and is no
        collective (the `while`s and `call`s around them left out)."""
        return [(s, e) for n, s, e in xplane.leaves(self.ops(chip))
                if not xplane.is_collective(n)]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> float:
        """Seconds an instruction ran, averaged over the chips used."""
        chips = range(len(self.trace.ops))
        return sum(intervals.total(self.busy(c)) for c in chips) / (
            1e9 * len(self.trace.ops))

    def breakdown(self, top: int = 10) -> dict:
        """The instructions of chip 0 that took most time (their own,
        without what they hold), and its idle time by the annotation the
        host was in."""
        own = intervals.self_times(
            (xplane.op_name(n), s, e) for n, s, e in self.ops(0))
        idle = intervals.attribute(
            intervals.gaps(self.busy(0), *self.window),
            [(n, s, e) for n, s, e in self.trace.host if n != WINDOW],
            default=WINDOW)

        def ranked(d):
            return [[k, v / 1e9] for k, v in
                    sorted(d.items(), key=lambda kv: -kv[1])[:top]]

        return {"device_ops": ranked(own), "idle_gaps": ranked(idle)}
