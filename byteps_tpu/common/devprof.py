"""Device/compute-plane profiler: live MFU, per-step device timers, and
the runtime device-fallback sentinel (``BYTEPS_TPU_DEVPROF=1``).

Every observability plane before this one watched the WIRE side; the
device side was a runtime blind spot — ``signals.py`` classified
``compute_bound`` purely from codec encode/decode time, the goodput
ledger's ``compute`` bucket was inferred residual rather than measured,
and nothing live noticed a job that had silently landed on the CPU
host platform.  This module is the device plane:

- **Per-step device timers**: the trainers bracket each jitted step
  with ``step_begin()``/``step_end()`` (dispatch → ``block_until_ready``
  delta).  Unarmed, both are one module-global read + ``None`` check —
  the hot-path law the signal plane set; in particular
  ``block_until_ready`` is only ever issued when the profiler is armed,
  so the unarmed dispatch pipeline is untouched.
- **Live MFU**: FLOPs per step come from the jitted fn's
  ``lower().compile().cost_analysis()`` — cached per compiled callable,
  gracefully ``None`` where the backend won't report — divided by the
  measured device seconds and the platform's peak FLOPs
  (spec-sheet table, ``BYTEPS_TPU_PEAK_FLOPS`` override) →
  ``bps_mfu{worker=}`` / ``bps_device_step_ms{worker=}`` gauges and a
  ``device`` section in every signal window summary.
- **Device lanes in the merged trace**: step spans are stamped on the
  same ``time.monotonic_ns()//1000`` µs timebase as
  ``core.trace_now_us()``, so they land in the merged ``comm.json``
  (pid = ``DEVICE_PID_BASE + rank``) already time-aligned with the wire
  spans.  A ``jax.profiler`` capture is laid beside them through the
  ``byteps.round`` annotations it holds (docs/timeline.md).
- **The device sentinel**: ``device_stamp()``'s platform probe, run
  at ``bps.init()`` and again on every signal-window roll; an
  intended-vs-actual platform mismatch
  (``BYTEPS_TPU_DEVICE_PLATFORM``) or a probe error convicts — doctor
  rule ``device_fallback`` (critical) fires within one window, and
  ``mfu_regression`` watches the windowed MFU trend with the wire held
  flat.  The sentinel starts no child process: a
  chip belongs to one process, so a child that probed the default
  backend while this one holds the chip could only fail or hang.

Cost model: ``BYTEPS_TPU_DEVPROF=0`` (default) arms nothing — zero
gauges, zero frames, wire byte-identical to the pre-PR stub recording
(asserted by tests/test_devprof.py).  Armed, the per-step cost is one
``block_until_ready`` (which a measuring caller wants anyway) plus a
short-lock dict update; the window roll is O(1) arithmetic plus the
stamp probe (module inspection only — it never *initializes* a
backend).
"""

from __future__ import annotations

import os
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from .logging import get_logger
from .trace_analysis import DEVICE_PID_BASE

SCHEMA = "bps-device-v1"

#: Peak dense bf16 FLOPs/s per chip by device kind (public spec
#: sheets).
PEAK_BF16 = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5": 459e12,        # v5p
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,   # v6e (Trillium)
    "TPU v6e": 918e12,
}

#: Bounded histories: trace spans kept for the comm.json merge and the
#: recent-step ring the flight recorder ships.
MAX_TRACE_SPANS = 4096
RECENT_STEPS = 64


def peak_flops(device=None, kind: Optional[str] = None) -> float:
    """Peak dense bf16 FLOPs/s for a device (or a device_kind string).

    ``BYTEPS_TPU_PEAK_FLOPS`` overrides.  A CPU host has no entry and
    returns 0.0 — MFU is then reported as ``None``, never a made-up
    number.  A TPU whose ``device_kind`` is missing from the table is
    an error, not a default: a wrong peak is a wrong MFU."""
    env = os.environ.get("BYTEPS_TPU_PEAK_FLOPS")
    if env:
        try:
            return float(env)
        except ValueError:
            get_logger().warning("unparseable peak-FLOPs override %r", env)
    if kind is None:
        kind = getattr(device, "device_kind", "") if device is not None \
            else ""
    for k, v in PEAK_BF16.items():
        if str(kind).startswith(k):
            return v
    if getattr(device, "platform", "") == "tpu":
        raise ValueError(
            f"device_kind {kind!r} is not in devprof.PEAK_BF16 "
            f"({sorted(PEAK_BF16)}); add its spec-sheet peak")
    return 0.0


def device_stamp() -> dict:
    """Platform-honesty stamp the live sentinel convicts by.

    ``device_platform`` is what the jax backend actually initialized as
    by stamp time — or ``"none(host-only)"`` when no backend was ever
    touched (detected WITHOUT initializing one: a host-only process
    must not claim the chip just to be stamped) — or ``"unknown(...)"``
    when the probe itself raised."""
    try:
        xb = sys.modules.get("jax._src.xla_bridge")
        if xb is None or not xb._backends:
            # jax never imported, or imported with no backend
            # initialized: a host-only process.
            return {"device_platform": "none(host-only)"}
        import jax
        platform = jax.devices()[0].platform
    except Exception as e:  # noqa: BLE001 — a stamp must never kill a record
        return {"device_platform": f"unknown({e!r:.60})"}
    return {"device_platform": platform}


def cost_analysis_flops(fn, args: tuple) -> Optional[float]:
    """FLOPs for one call of a jitted fn, via
    ``lower(*args).compile().cost_analysis()``.  ``None`` whenever the
    backend won't report (CPU backends often return no ``flops`` key) —
    the caller downgrades to time-only reporting, never fails."""
    try:
        cost = fn.lower(*args).compile().cost_analysis()
    except Exception:
        return None
    if not isinstance(cost, dict):
        return None
    flops = cost.get("flops")
    if not isinstance(flops, (int, float)) or flops <= 0:
        return None
    return float(flops)


class DeviceProfiler:
    """The armed device plane for one process (module singleton below).

    Thread model: ``note_step`` lands on the trainer thread,
    ``window_roll`` on the signal-window thread, ``profile`` /
    ``flight_section`` on any reader — every shared field mutates under
    one short lock."""

    def __init__(self, intended_platform: str = "", worker: int = 0,
                 telemetry_on: bool = True):
        self.intended = str(intended_platform or "")
        self.worker = int(worker)
        self.telemetry_on = bool(telemetry_on)
        self._lock = threading.Lock()
        # lifetime totals
        self.steps_total = 0
        self.device_s_total = 0.0
        # current-window accumulators (drained by window_roll)
        self._win_steps = 0
        self._win_device_s = 0.0
        self._win_flops = 0.0
        self._win_flops_s = 0.0     # device seconds of flops-known steps
        # bounded histories
        self._spans: deque = deque(maxlen=MAX_TRACE_SPANS)
        self._recent_ms: deque = deque(maxlen=RECENT_STEPS)
        # cost_analysis cache: one lower+compile per jitted callable,
        # not per step (the unit suite pins this).
        self._flops_cache: Dict[int, Optional[float]] = {}
        self.cost_cache_hits = 0
        self.cost_cache_misses = 0
        self._peak: Optional[float] = None
        self._last_probe: Optional[dict] = None
        self._last_window: Optional[dict] = None

    # -- per-step feed ------------------------------------------------------
    def flops_for(self, fn, args: tuple) -> Optional[float]:
        key = id(fn)
        with self._lock:
            if key in self._flops_cache:
                self.cost_cache_hits += 1
                return self._flops_cache[key]
        val = cost_analysis_flops(fn, args)
        with self._lock:
            self.cost_cache_misses += 1
            self._flops_cache[key] = val
        return val

    def note_step(self, t0_ns: int, t1_ns: int,
                  flops: Optional[float] = None) -> None:
        dur_ns = max(0, int(t1_ns) - int(t0_ns))
        dev_s = dur_ns / 1e9
        with self._lock:
            self.steps_total += 1
            self.device_s_total += dev_s
            self._win_steps += 1
            self._win_device_s += dev_s
            if flops:
                self._win_flops += float(flops)
                self._win_flops_s += dev_s
            self._spans.append((int(t0_ns) // 1000,
                                max(1, dur_ns // 1000), self.steps_total))
            self._recent_ms.append(round(dev_s * 1000.0, 3))

    # -- sentinel -----------------------------------------------------------
    def probe(self) -> dict:
        """One sentinel pass: stamp the backend, convict a fallback.

        Conviction law: a probe ERROR (``unknown(...)`` platform — jax
        internals moved, or the backend raised mid-run) always convicts;
        an intended platform (``BYTEPS_TPU_DEVICE_PLATFORM``) convicts
        on mismatch once a backend actually initialized.  A bare-CPU
        run with NO intent declared is healthy — the tier-1 suite and
        every local dev loop run exactly like that, and a sentinel that
        cried wolf there would be disarmed within a week.
        ``"none(host-only)"`` with an intent declared stays quiet too:
        no backend has been touched yet, so there is nothing to convict
        (the first trainer step changes that)."""
        platform = str(device_stamp()["device_platform"])
        fallback, reason = False, ""
        if platform.startswith("unknown("):
            fallback = True
            reason = f"device probe failed: {platform}"
        elif self.intended and not platform.startswith("none(") \
                and platform != self.intended:
            fallback = True
            reason = (f"intended platform {self.intended!r} but the jax "
                      f"backend initialized as {platform!r}")
        probe = {"platform": platform,
                 "intended": self.intended,
                 "fallback": fallback,
                 "reason": reason}
        with self._lock:
            self._last_probe = probe
        return dict(probe)

    # -- window roll (the signals provider) ---------------------------------
    def _peak_flops(self) -> float:
        if self._peak is not None:
            return self._peak
        kind = ""
        try:
            xb = sys.modules.get("jax._src.xla_bridge")
            if xb is not None and xb._backends:
                import jax
                kind = jax.devices()[0].device_kind
        except Exception:
            kind = ""
        self._peak = peak_flops(kind=kind)
        return self._peak

    def window_roll(self) -> dict:
        """Close one device window: re-probe the sentinel, drain the
        step accumulators, compute MFU, update the gauges.  Returns the
        ``device`` section the signal window summary carries (and the
        doctor rules read)."""
        probe = self.probe()
        with self._lock:
            steps = self._win_steps
            dev_s = self._win_device_s
            flops = self._win_flops
            flops_s = self._win_flops_s
            self._win_steps = 0
            self._win_device_s = 0.0
            self._win_flops = 0.0
            self._win_flops_s = 0.0
        device_step_ms = (1000.0 * dev_s / steps) if steps else None
        mfu = None
        flops_per_s = None
        peak = self._peak_flops()
        if flops > 0.0 and flops_s > 0.0:
            flops_per_s = flops / flops_s
            if peak > 0.0:
                mfu = flops_per_s / peak
        sec = {
            "schema": SCHEMA,
            "probe": probe,
            "platform": probe["platform"],
            "steps": steps,
            "compute_s": round(dev_s, 6),
            "device_step_ms": (round(device_step_ms, 3)
                               if device_step_ms is not None else None),
            "mfu": round(mfu, 6) if mfu is not None else None,
            "flops_per_s": flops_per_s,
            "peak_flops": peak if peak > 0.0 else None,
        }
        with self._lock:
            self._last_window = sec
        if self.telemetry_on:
            self._update_gauges(sec)
        return dict(sec)

    def _update_gauges(self, sec: dict) -> None:
        from .telemetry import get_registry
        reg = get_registry()
        w = str(self.worker)
        if sec["device_step_ms"] is not None:
            reg.gauge("bps_device_step_ms",
                      help="mean on-device step time over the last "
                           "signal window (dispatch -> block_until_ready)",
                      labels={"worker": w}).set(sec["device_step_ms"])
        if sec["mfu"] is not None:
            reg.gauge("bps_mfu",
                      help="model FLOPs utilization over the last signal "
                           "window (cost_analysis FLOPs / device seconds "
                           "/ platform peak)",
                      labels={"worker": w}).set(sec["mfu"])
        reg.gauge("bps_device_fallback",
                  help="1 when the device sentinel convicted a platform "
                       "fallback or probe error (0 = on the intended "
                       "chip); the platform label names what the "
                       "backend actually initialized as",
                  labels={"worker": w,
                          "platform": sec["platform"]}).set(
                      1.0 if (sec["probe"] or {}).get("fallback") else 0.0)

    # -- read surfaces ------------------------------------------------------
    def profile(self) -> dict:
        """The ``bps.get_device_profile()`` payload."""
        with self._lock:
            steps = self.steps_total
            dev_s = self.device_s_total
            recent = list(self._recent_ms)
            probe = dict(self._last_probe) if self._last_probe else None
            last = dict(self._last_window) if self._last_window else None
            cache = {"hits": self.cost_cache_hits,
                     "misses": self.cost_cache_misses,
                     "entries": len(self._flops_cache)}
        return {
            "armed": True,
            "schema": SCHEMA,
            "worker": self.worker,
            "intended": self.intended,
            "probe": probe,
            "platform": (probe or {}).get("platform"),
            "steps_total": steps,
            "device_s_total": round(dev_s, 6),
            "mean_step_ms": (round(1000.0 * dev_s / steps, 3)
                             if steps else None),
            "recent_step_ms": recent,
            "last_window": last,
            "mfu": (last or {}).get("mfu"),
            "peak_flops": self._peak,
            "cost_cache": cache,
        }

    def flight_section(self) -> dict:
        """Flight-recorder provider: the ``device`` bundle section
        (sections merge FLAT into the bundle's ``extra``, hence the
        wrapping key).  Enough to answer "was it on-chip?" from the
        bundle alone: last sentinel probe, last-window MFU, and the
        recent device-step history."""
        with self._lock:
            return {"device": {
                "schema": SCHEMA,
                "probe": dict(self._last_probe) if self._last_probe
                else None,
                "last_window": dict(self._last_window)
                if self._last_window else None,
                "steps_total": self.steps_total,
                "device_s_total": round(self.device_s_total, 6),
                "recent_step_ms": list(self._recent_ms),
            }}

    # -- trace lanes --------------------------------------------------------
    def trace_events(self, rank: int = 0) -> List[dict]:
        """Self-recorded device-step spans as Chrome events on the
        device lane (pid = DEVICE_PID_BASE + rank).  Already on the
        worker's monotonic-µs timebase — the same clock the wire spans
        use — so the merge needs no offset."""
        pid = DEVICE_PID_BASE + int(rank)
        with self._lock:
            spans = list(self._spans)
        return [{"name": f"device_step_{i}", "cat": "device", "ph": "X",
                 "ts": ts, "dur": dur, "pid": pid, "tid": "DEVICE",
                 "args": {"step": i}}
                for ts, dur, i in spans]


# ---------------------------------------------------------------------------
# Module singleton + hot-path hooks: unarmed cost is ONE global read and
# a None check per call site (the signals-plane law).
# ---------------------------------------------------------------------------
_prof: Optional[DeviceProfiler] = None
_prof_lock = threading.Lock()


def active() -> Optional[DeviceProfiler]:
    return _prof


def arm(intended_platform: str = "", worker: int = 0,
        telemetry_on: bool = True) -> DeviceProfiler:
    """Install the process-wide device profiler.  Idempotent per
    process: re-arming replaces the previous profiler."""
    global _prof
    with _prof_lock:
        _prof = DeviceProfiler(intended_platform=intended_platform,
                               worker=worker, telemetry_on=telemetry_on)
        return _prof


def disarm() -> None:
    global _prof
    with _prof_lock:
        _prof = None


def step_begin(fn=None, args: Optional[tuple] = None
               ) -> Optional[Tuple[int, Optional[float]]]:
    """Trainer hook, called right before dispatching the jitted step.

    Returns ``None`` when unarmed (the trainer then skips
    ``step_end``'s sync entirely).  Armed, resolves the step's FLOPs
    FIRST (cached per callable; ``cost_analysis`` needs only abstract
    shapes, but resolving pre-call keeps it clear of donated buffers)
    and stamps the dispatch time."""
    p = _prof
    if p is None:
        return None
    flops = p.flops_for(fn, args or ()) if fn is not None else None
    return (time.monotonic_ns(), flops)


def step_end(token: Optional[Tuple[int, Optional[float]]],
             out: Any = None) -> None:
    """Trainer hook, called with ``step_begin``'s token after the
    dispatch returns.  Blocks on ``out`` (the device sync that makes
    the delta a DEVICE time, issued ONLY here — the unarmed path never
    syncs) and records the step."""
    p = _prof
    if p is None or token is None:
        return
    if out is not None:
        try:
            import jax
            jax.block_until_ready(out)
        except Exception:
            pass
    t0_ns, flops = token
    p.note_step(t0_ns, time.monotonic_ns(), flops=flops)
