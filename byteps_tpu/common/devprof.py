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
import re
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


# ---------------------------------------------------------------------------
# The scope map of the compiled step (`bps.get_step_scopes()`).
#
# A `jax.named_scope` in the program is a span that costs nothing: it
# changes no operation, only the `op_name` metadata of the instructions
# traced under it, which holds JAX's name stack,
#
#     jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/
#         rematted_computation/transformer.mlp/dot_general
#
# (a transform wraps the ONE component after it: `jvp(afmoe.moe)/.route`).
# The device trace names instructions and nothing else; the compiled
# module says where each came from.  So the trainer remembers, at each
# compile of its callable, what it compiled (`remember_step`), and on
# request the module's text is parsed into {instruction: scope, pass}.
#
# The vocabulary is a matter of FORM, and this module knows no model's
# names: a scope the program opened is a component of the path with a
# "." in it (`<family>.<part>`: `mellum.moe`, `byteps.optimizer`); JAX's
# own components (`while`, `body`, `checkpoint`, `closed_call`, ...) and
# a module system's have none, and a function's name stands in `jit()`.
# A scope's child is opened with a RELATIVE name, a leading "."
# (`.qkv` inside `mellum.attn.full_attention`), and the map drops that
# dot: "mellum.attn.full_attention/qkv".  A new family brings its scopes
# in its own files and nothing here changes.
# ---------------------------------------------------------------------------
#: Whatever this scope holds runs in pass "optimizer"; the trainer opens
#: it (parallel/data_parallel.py).
OPTIMIZER_SCOPE = "byteps.optimizer"
PASSES = ("forward", "backward", "recompute", "optimizer", "other")

_WRAPPED = re.compile(r"^([\w\-.]+)\((.*)\)$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+) = (.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) \(.*\) -> .* \{$")
_OPCODE = re.compile(r"(?<![\w.\-])([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(
    r"(?:calls|body|condition|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_OPERAND = re.compile(r"%([\w.\-]+)")
#: Instructions whose called computations hold instructions that run, and
#: show in a trace, as themselves.
_CONTROL_FLOW = ("while", "call", "conditional", "async-start")
#: Instructions that only hand a value on: a kernel's operand is made by
#: whatever stands behind them.
_THIN = ("get-tuple-element", "bitcast", "copy", "copy-start", "copy-done")
#: What a fusion is named after if it holds one: the matrix product
#: (which the TPU compiler writes as a convolution) or grouped product.
_PRODUCTS = ("convolution", "dot", "ragged-dot")


def classify_op_name(op_name: str) -> Tuple[str, str]:
    """`(scope, pass)` of one `op_name`.  The scope is the program's own
    components of the path, joined by `/` ("" where it has none): those
    with a "." in them that are no function's name (`jit(...)`), a
    child's leading "." dropped; the last component, the primitive's
    name, never is one.  The pass, by rule: under `byteps.optimizer` ->
    optimizer; under `rematted_computation` -> recompute; else under
    `transpose(` -> backward; else under `jvp(` -> forward; else other.
    Where the compiler made one instruction of two it writes both paths,
    `a/b/x;b/y`: the first is read."""
    parts = op_name.split(";")[0].split("/")
    scope: List[str] = []
    transforms = set()
    for i, part in enumerate(parts):
        m = _WRAPPED.match(part)
        while m:
            transforms.add(m.group(1))
            if m.group(1) in ("jit", "pjit"):
                part = ""           # a function's name, no scope
                break
            part = m.group(2)
            m = _WRAPPED.match(part)
        if part == "rematted_computation":
            transforms.add(part)
        elif i < len(parts) - 1 and "." in part:
            scope.append(part.lstrip(".") if scope else part)
    if OPTIMIZER_SCOPE in scope:
        which = "optimizer"
    elif "rematted_computation" in transforms:
        which = "recompute"
    elif "transpose" in transforms:
        which = "backward"
    elif "jvp" in transforms:
        which = "forward"
    else:
        which = "other"
    return "/".join(scope), which


def parse_step_scopes(hlo_text: str) -> Dict[str, dict]:
    """`{instruction: {"scope", "pass", "op_name"}}` for every instruction
    of an optimized HLO module (`compiled.as_text()`) that can run as
    itself: those of the entry computation and of the computations a
    `while`, `call` or `conditional` reaches from it.  The instructions
    INSIDE a fused computation are left out (a trace shows the fusion),
    and so are the scalar computations of a `reduce` or a `sort`.

    A fusion's path, by rule: that of the matrix product, convolution or
    grouped product it holds, if it holds one (a scan's write of a
    layer's gradient into its stack is fused around whatever computes
    the gradient: the root alone would say "the scan"); else its own
    metadata's, which is its root's; else, where that has no program
    scope, the first inner instruction's that has one.

    One kind of instruction is LENT a scope, and its entry says so
    (`"lent": True`): a kernel the compiler made itself, a `custom-call`
    whose `op_name` is no path of JAX's (`ragged-dot-none`, the grouped
    product the TPU compiler builds out of a `lax.ragged_dot` whose path
    it drops: the expert layer's at a width its own kernels cannot tile,
    `ops/grouped_matmul.py`; those carry their path like any Pallas call
    and are lent nothing).  It takes what the scopes of its neighbours share: of the
    instructions that make its operands and of those that read its
    result, behind any that only hand a value on, those whose scope is
    their own; and the pass of the first reader that has one, else of
    the first maker.  A grouped product between the gather and the
    activation lies somewhere in `mellum.moe`: no more can be said, so
    no more is.  Everything else without a scope stays without: what the
    compiler hoists out of a loop and strips of its path is listed by a
    reader, not guessed at.  A pure function of the text."""
    comps: Dict[str, list] = {}
    entry = current = None
    for line in hlo_text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = comps.setdefault(m.group(1), [])
                if line.startswith("ENTRY"):
                    entry = m.group(1)
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        rest = m.group(3)
        code = _OPCODE.search(rest)
        name = _OP_NAME.search(rest)
        called = _CALLED.findall(rest)
        for group in _BRANCHES.findall(rest):
            called += [c.strip().lstrip("%") for c in group.split(",")]
        current.append((m.group(2), code.group(1) if code else "",
                        name.group(1) if name else "", called,
                        _OPERAND.findall(rest.split(", metadata=")[0])))

    def inner(comp: str, seen=None):
        """Every instruction under a fused computation, nested ones too."""
        seen = set() if seen is None else seen
        if comp in seen:
            return
        seen.add(comp)
        for ins in comps.get(comp, ()):
            yield ins
            if ins[1] == "fusion":
                for c in ins[3]:
                    yield from inner(c, seen)

    def fusion_path(own: str, called: list) -> str:
        first = ""
        for c in called:
            for _, code, op_name, _, _ in inner(c):
                if code in _PRODUCTS and op_name:
                    return op_name
                if not first and op_name and classify_op_name(op_name)[0]:
                    first = op_name
        if own and classify_op_name(own)[0]:
            return own
        return first or own

    out: Dict[str, dict] = {}
    todo, walked = [entry] if entry else [], set()
    while todo:
        comp = todo.pop()
        if comp in walked:
            continue
        walked.add(comp)
        table: Dict[str, tuple] = {}    # instruction -> (opcode, operands)
        reads: Dict[str, list] = {}     # instruction -> those that read it
        kernels = []
        for ins_name, code, op_name, called, operands in comps.get(comp, ()):
            if code in _CONTROL_FLOW:
                todo.extend(called)
            elif code == "fusion":
                op_name = fusion_path(op_name, called)
            scope, which = classify_op_name(op_name)
            out[ins_name] = {"scope": scope, "pass": which,
                             "op_name": op_name}
            table[ins_name] = (code, operands)
            for operand in operands:
                reads.setdefault(operand, []).append(ins_name)
            if code == "custom-call" and op_name and "/" not in op_name:
                kernels.append(ins_name)
        for ins_name in kernels:
            _lend(out, ins_name,
                  _behind(table[ins_name][1], table, lambda n: table[n][1]),
                  _behind(reads.get(ins_name, ()), table,
                          lambda n: reads.get(n, ())))
    return out


def _behind(names, table: dict, following) -> list:
    """`names`, each that only hands a value on (`_THIN`) replaced by what
    stands behind it along `following`; those of another computation (a
    parameter's, a loop's) are left out."""
    found, todo, seen = [], list(names), set()
    while todo:
        n = todo.pop(0)
        if n in seen or n not in table:
            continue
        seen.add(n)
        if table[n][0] in _THIN:
            todo.extend(following(n))
        else:
            found.append(n)
    return found


def _lend(out: dict, kernel: str, makers: list, readers: list) -> None:
    """Gives `kernel`'s entry what the own scopes of `makers` and
    `readers` share, and a reader's pass (`parse_step_scopes`)."""
    def own(n):
        return n in out and out[n]["scope"] and not out[n].get("lent")
    paths = [out[n]["scope"].split("/") for n in makers + readers if own(n)]
    shared = os.path.commonprefix(paths) if paths else None
    if not shared:
        return
    passed = [out[n]["pass"] for n in readers + makers
              if n in out and out[n]["pass"] != "other"]
    out[kernel].update(scope="/".join(shared), lent=True,
                       **({"pass": passed[0]} if passed else {}))


#: `(callable, abstract arguments)` of the last train step a trainer
#: compiled.  Module-level: like the registry's gauges it outlives
#: `bps.shutdown()`, so that whoever traced a job can still ask.
_step_record: Optional[tuple] = None
_step_scopes: Optional[tuple] = None     # (record, the map or None)


def remember_step(fn, args: tuple) -> None:
    """Trainer hook, called after a call of `fn` that compiled it (the
    first; a later one where the shapes or the placement changed) and on
    no other step: keeps the callable and the shapes, dtypes and
    shardings of `args`, which hold no buffer.  `build_train_step` passes
    the step's own results in place of the state it was given: they are
    what every later step receives (on a mesh the first call's arguments
    lie elsewhere, and that program is not the one that goes on
    running)."""
    global _step_record
    import jax
    import jax.numpy as jnp
    if any(isinstance(a, jax.core.Tracer) for a in jax.tree.leaves(args)):
        return      # traced into somebody else's program: not ours to map

    def abstract(a):
        # An array nobody placed is lowered with no sharding, as the call
        # itself lowers it: the same program, found again and not
        # compiled a second time.
        placed = getattr(a, "committed", False)
        return jax.ShapeDtypeStruct(
            jnp.shape(a), jnp.result_type(a),
            sharding=a.sharding if placed else None,
            weak_type=getattr(a, "weak_type", False))
    _step_record = (fn, jax.tree.map(abstract, args))


def get_step_scopes() -> Optional[Dict[str, dict]]:
    """The scope map of the last train step `build_train_step` compiled
    (`parse_step_scopes` says what it holds), or None where no step was
    built.  On the first request the step's callable is lowered for the
    remembered shapes, for which JAX hands back the executable the step
    holds (nothing is compiled, and the map is of what ran), and its text
    parsed; the map is kept.  Never raises: where the lowering, the
    compile or the parse fails the answer is None, with one logged
    line."""
    global _step_scopes
    record = _step_record
    if record is None:
        return None
    if _step_scopes is not None and _step_scopes[0] is record:
        return _step_scopes[1]
    fn, args = record
    t0 = time.monotonic()
    try:
        from ..utils import compile_cache
        # As the step was compiled; and what this makes, should it make
        # anything, is no recompile of the job's.
        with compile_cache.scopes_in_key(), \
                compile_cache.caused_by("scope_map"):
            text = fn.lower(*args).compile().as_text()
        scopes = parse_step_scopes(text)
        get_logger().info("scope map of the step: %d instructions in "
                          "%.2f s", len(scopes), time.monotonic() - t0)
    except Exception as e:  # noqa: BLE001 — a reader must not end a run
        get_logger().warning("no scope map of the step: %r", e)
        scopes = None
    _step_scopes = (record, scopes)
    return scopes


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
                      help="FLOPs utilization over the last signal window "
                           "(cost_analysis FLOPs / device seconds / "
                           "platform peak); cost_analysis counts what the "
                           "step executes, recomputed operations included",
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
