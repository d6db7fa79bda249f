"""One run of one cell: set-up, the checks that decide `correct`, the
measured window or the traced one, and the result line."""

from __future__ import annotations

import importlib
import math
import os
import time

import jax

from benchmark.harness import (chip, correct, manifest, readers, seeded,
                               tracecap)
from benchmark.reduce import xplane

# Steps of the measured path before the window, each waited for; the last
# is made with the job's own checks on.  Their losses are what is compared
# with the plain steps where gradients are exchanged.  On a mesh the
# program compiles its step a second time in the second of them
# (PERF.md): at least two are needed for the window to be free of
# compilation.
WARMUP_STEPS = 3
# Steady steps under the profiler in a traced run.
TRACE_STEPS = 5
TRACE_ROOT = os.path.join(manifest.ROOT, ".bench_trace")


class CompileCounter:
    """Counts the programs this process asks the backend to compile (a
    hit in the persistent cache counts too: it is a program the window
    had not seen), from JAX's own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kwargs) -> None:
        if event == self.EVENT:
            self.count += 1


def run_steps(job, seconds=None, steps=None):
    """Steps with one in flight: step i+1 is dispatched, then the loss of
    step i is waited for, as a user's loop that logs its loss does.  Runs
    until `seconds` have passed (the step in flight then is the last) or
    for `steps` steps.  Returns the losses and the time each step's loss
    arrived, from the start."""
    losses, arrived = [], []
    t0 = time.perf_counter()
    pending, dispatched = job.step(), 1
    while pending is not None:
        if steps is None:
            more = time.perf_counter() - t0 < seconds
        else:
            more = dispatched < steps
        following = job.step() if more else None
        dispatched += more
        with jax.profiler.TraceAnnotation("bench.wait"):
            losses.append(float(pending))
        arrived.append(time.perf_counter() - t0)
        pending = following
    return losses, arrived


def _module(kind: str, name: str):
    path = os.path.join(manifest.BENCH, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: no {path}")
    return importlib.import_module(f"benchmark.{kind}.{name}")


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             peaks: dict, t_start: float):
    """Returns `(result line, detail)`.  `devices` are the chips the cell
    uses; `t_start` is when the process started, on `perf_counter`."""
    family = _module("families", cell.config["family"]).Family(
        cell.config, cell.job)
    window = None
    if trace:
        window = tracecap.TraceWindow(
            dir=os.path.join(TRACE_ROOT, cell.name),
            first_step=WARMUP_STEPS, n_steps=TRACE_STEPS)
    job = _module("jobs", cell.traffic["job"]).Job(
        family, cell.job, cell.traffic, devices, seed, trace=window)
    compiles = CompileCounter()
    memory = chip.MemoryWatch(devices)
    checks, detail, phases = {}, {}, {}

    def phase_done(name: str) -> None:
        memory.sample()
        phases[name] = time.perf_counter() - t_start

    phase_done("start")
    reference = correct.reference_check(family, seed)
    checks["reference"] = reference.pop("ok")
    detail["reference"] = reference
    phase_done("reference_check")

    plain = None
    if job.plain_kind:
        # Before the measured path is built: two copies of parameters and
        # optimizer state do not fit beside a step's temporaries.
        plain = correct.plain_losses(
            family, seeded.params(family, seed),
            seeded.shards(family, seed, job.samples_per_step, job.n_shards),
            WARMUP_STEPS)
        phase_done("plain_steps")

    with job:
        phase_done("job_built")
        warm = [float(job.step()) for _ in range(WARMUP_STEPS - 1)]
        loss, before = job.checked_step()
        warm.append(float(loss))
        if plain is not None:
            detail["plain_step"] = correct.losses_agree(warm, plain,
                                                        job.plain_kind)
            checks["plain_step"] = detail["plain_step"]["ok"]
        phase_done("warm_up")
        setup_s = time.perf_counter() - t_start
        compiled_before = compiles.count
        if trace:
            with tracecap.capture(window.dir):
                losses, arrived = run_steps(job, steps=TRACE_STEPS)
        else:
            losses, arrived = run_steps(job, seconds=seconds)
        compiled_in_window = compiles.count - compiled_before
        phase_done("window")
        loss, after = job.checked_step()
        float(loss)
        extras = job.extras()

    checks.update({f"{k}.before": v for k, v in before.items()})
    checks.update({f"{k}.after": v for k, v in after.items()})
    checks["no_compile_in_window"] = compiled_in_window == 0
    checks["losses_sound"] = correct.losses_sound(losses)
    detail.update(
        checks=checks, steps=len(losses), window_s=arrived[-1],
        compiled_before_window=compiled_before,
        compiled_in_window=compiled_in_window, warmup_losses=warm,
        phase_ended_s=phases, loss_arrived_s=arrived,
        first_loss=losses[0], last_loss=losses[-1])

    device = chip.describe(devices, memory.peak)
    line = {"correct": all(checks.values()), "attempted": len(losses),
            "failed": sum(not math.isfinite(x) for x in losses)}
    if trace:
        path = xplane.find(window.dir)
        if path is None:
            raise SystemExit(f"benchmark: the profiler wrote no trace under "
                             f"{window.dir}")
        ctx = tracecap.Context(
            trace=xplane.read(path, host_prefix=tracecap.PREFIX),
            n_steps=len(losses), first_step=window.first_step,
            n_chips=len(devices), samples_per_step=job.samples_per_step,
            family=family, peaks=peaks, extras=extras, dir=window.dir)
        line["metrics"] = readers.read_all(cell.per_layer, ctx)
        if ctx.window is not None and ctx.trace.ops:
            device["busy_s"] = ctx.busy_s()
            device["window_s"] = ctx.window_s
            line["breakdown"] = ctx.breakdown()
    else:
        throughput = (job.samples_per_step * family.units_per_sample
                      * len(losses) / arrived[-1])
        found = {f"{job.metric_prefix}{family.unit}_per_s": throughput,
                 "setup_s": setup_s}
        missing = [m["name"] for m in cell.end_to_end
                   if m["name"] not in found]
        if missing:
            raise SystemExit(f"benchmark: {cell.name} lists {missing}, "
                             f"which this run does not measure")
        line["metrics"] = {m["name"]: {"value": found[m["name"]],
                                       "unit": m["unit"]}
                           for m in cell.end_to_end}
    line["device"] = device
    return line, detail
