"""One place that decides where JAX's persistent compilation cache lives,
what the train step's entry in it is keyed by (`scopes_in_key`), and the
process's one log of what it traced, lowered and compiled (`CompileLog`).

The cache is for the scripts that run on the chip (chip_smoke.py,
benchmark/run.py, tools/reference_check.py, tools/afmoe_drift.py).  The
library itself (`bps.init`) sets no cache; it does install the log.

The rule: where `JAX_COMPILATION_CACHE_DIR` is set, the cache was placed
from outside — JAX reads the variable itself and nothing is set in code.
Otherwise the cache goes to ONE fixed directory inside the checkout
(gitignored).  The path is part of the cache's key, so a directory built
from a temp name, a pid or the time would never hit.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time

ENV = "JAX_COMPILATION_CACHE_DIR"

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_REPO, ".jax_cache")


def cache_dir() -> str:
    """The directory the cache is (or would be) in.  Touches no JAX: a
    parent that only starts children puts this in their environment."""
    return os.environ.get(ENV) or DEFAULT_DIR


def enable() -> str:
    """Turn the persistent cache on for this process, and the compile
    log with it; returns the cache's directory.  Call before the first
    compile."""
    install()
    path = cache_dir()
    if not os.environ.get(ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path


@contextlib.contextmanager
def scopes_in_key():
    """Programs compiled inside are keyed, in the persistent cache, by
    their operations AND their instructions' name stacks, so by the
    `jax.named_scope`s the program opened; this thread only.

    JAX's default key leaves all metadata out: a tree whose operations
    equal an older tree's is handed that tree's executable, with that
    tree's scopes and its names for unnamed kernel calls, and
    `bps.get_step_scopes()`, which reads the executable that ran, would
    describe those.  JAX's other key takes ALL metadata in, source lines
    among it, and a comment added to a file a step is traced through
    would compile the step anew.  So the tracebacks are left out of what
    is lowered here (`jax_traceback_in_locations_limit` 0: the step's
    instructions then carry their `op_name` and no source location, the
    `stack_frame_id` a profile's source view reads), and the rest is the
    key: a renamed scope or a changed operation misses, a moved line
    hits.  `build_train_step` compiles the train step so, and nothing
    else is."""
    global _key_states
    if _key_states is None:
        try:    # thread-local context managers; JAX exports the values only
            from jax._src import config
            _key_states = (config.compilation_cache_include_metadata_in_key,
                           config.traceback_in_locations_limit)
        except (ImportError, AttributeError):
            _key_states = ()        # a JAX without them: its own key
    if not _key_states:
        yield
        return
    metadata_in_key, traceback_limit = _key_states
    with metadata_in_key(True), traceback_limit(0):
        yield


_key_states = None     # the two config states, () where JAX has none




# ---------------------------------------------------------------------------
# The compile log: what this process traced, lowered and compiled, by
# program, from JAX's own monitoring events.
# ---------------------------------------------------------------------------
#: Each stage sends `record_scalar(event, start, fun_name=)` as it is
#: entered and `record_event_time_span(event, start, end, fun_name=)` as
#: it is left (jax/_src/dispatch.py `LogElapsedTimeContextManager`);
#: `fun_name` is `f` for a trace and `jit(f)` for the other two.
_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "TRACE",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "LOWER",
    "/jax/core/compile/backend_compile_duration": "COMPILE",
}
KINDS = ("TRACE", "LOWER", "COMPILE")
#: The persistent cache's events arrive on the compiling thread between
#: a COMPILE's entry and its exit.  `miss` is sent when the entry is
#: WRITTEN, so a program the cache was asked for, did not hold and will
#: not keep sends `asked` alone.
_CACHE_SAID = {
    "/jax/compilation_cache/compile_requests_use_cache": "asked",
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
#: What the cache answered a COMPILE: `hit` (retrieved, `retrieval_s`);
#: `miss` (compiled and written: the next run hits); `small` (asked, not
#: held, compiled in less than the cache keeps,
#: `jax_persistent_cache_min_compile_time_secs`: every run compiles it
#: again, cheaply); `uncached` (compiled, and neither held nor kept
#: though it took long enough: the cache is off, or the program is one it
#: refuses, as one with a `jax.debug.callback` in it is).
CACHE_ANSWERS = ("hit", "miss", "small", "uncached")
MAX_RECORDS = 2048
#: A TRACE shorter than this is JAX finding a jaxpr it already holds (an
#: eager `jnp` call whose arguments miss the fast path sends one, 25 us
#: each and thousands a set-up; `lower().compile()` of a callable that
#: holds its executable sends one of length zero): counted (`brief`),
#: and no record, or the records of a set-up would be these.
BRIEF_TRACE_S = 1e-3


class _ThreadState:
    """What one thread has open: per kind `[depth, spans nested in the
    outermost one]`, the cache's events since its COMPILE was entered,
    and the cause the program gave for what it is about to compile."""

    __slots__ = ("spans", "cache", "cause")

    def __init__(self):
        self.spans = {kind: [0, 0] for kind in KINDS}
        self.cache = {}
        self.cause = None


class CompileLog:
    """A record for every OUTERMOST span of a stage on its thread; spans
    nested in it (a jitted `jnp` function traced inside a step sends its
    own events: a model's trace is thousands of them) are counted into
    it, so that a listener call is a few integer operations.  A TRACE
    under `BRIEF_TRACE_S` is counted and makes no record.  At most
    `MAX_RECORDS` records are kept; the totals go on counting.

    `steady_at` is where set-up ends, told by the program: the start of
    the first call of an entry point a user calls once a step
    (`build_train_step`'s callable, `push_pull_tree`) during which no
    record was made on any thread.  A COMPILE that starts after it, with
    no cause of the program's own, is a recompile."""

    def __init__(self):
        self.installed_at = time.time()
        # The core tracer's clock (`core.trace_now_us`: steady_clock, as
        # `time.monotonic`) minus the wall clock JAX stamps events with.
        self.offset_us = time.monotonic_ns() / 1e3 - self.installed_at * 1e6
        self.process_start = _process_start()
        self.steady_at = None
        self.records: list = []
        self.made = 0           # records made, kept or not
        self.open = 0           # outermost spans open now, on any thread
        self.recompiles = 0
        self.by_kind = {kind: {"records": 0, "nested": 0, "seconds": 0.0}
                        for kind in KINDS}
        self.by_kind["TRACE"]["brief"] = 0
        self.by_cache = dict.fromkeys(CACHE_ANSWERS, 0)
        self._frontier = dict.fromkeys(KINDS, 0.0)
        self._lock = threading.Lock()
        self._local = threading.local()

    def _thread(self) -> _ThreadState:
        try:
            return self._local.state
        except AttributeError:
            state = self._local.state = _ThreadState()
            return state

    # -- the listeners' side -------------------------------------------------
    def enter(self, kind: str) -> None:
        state = self._thread()
        span = state.spans[kind]
        if span[0]:
            span[1] += 1
        else:
            span[1] = 0
            if kind == "COMPILE":
                state.cache = {}
            with self._lock:
                self.open += 1
        span[0] += 1

    def leave(self, kind: str, start: float, end: float, name) -> None:
        state = self._thread()
        span = state.spans[kind]
        if span[0] > 1:
            span[0] -= 1
            return
        entered, span[0] = span[0], 0   # 0: installed inside this span
        if kind == "TRACE" and end - start < BRIEF_TRACE_S:
            with self._lock:
                self.open -= entered
                self.by_kind[kind]["brief"] += 1
            return
        record = {"kind": kind, "name": name, "start": start, "end": end,
                  "start_us": int(start * 1e6 + self.offset_us),
                  "end_us": int(end * 1e6 + self.offset_us),
                  "thread": threading.get_ident(), "nested": span[1],
                  "cause": state.cause}
        if kind == "COMPILE":
            record["cache"] = _cache_answer(state.cache, end - start)
            if "retrieval_s" in state.cache:
                record["retrieval_s"] = state.cache["retrieval_s"]
        with self._lock:
            self.open -= entered
            record["seq"] = self.made
            self.made += 1
            if len(self.records) < MAX_RECORDS:
                self.records.append(record)
            total = self.by_kind[kind]
            total["records"] += 1
            total["nested"] += span[1]
            # The union of the kind's spans, kept as a running sum: exact
            # where they arrive in the order they ended and do not
            # overlap, as one thread's do; where two threads compile at
            # once, what the later one adds past the other's end.
            total["seconds"] += max(0.0, end - max(start,
                                                   self._frontier[kind]))
            self._frontier[kind] = max(self._frontier[kind], end)
            if kind == "COMPILE":
                self.by_cache[record["cache"]] += 1
            late = (kind == "COMPILE" and record["cause"] is None
                    and self.steady_at is not None
                    and start >= self.steady_at)
            self.recompiles += late
        if late:
            _warn_of_recompile(record)

    def cache_said(self, key: str, value=True) -> None:
        self._thread().cache[key] = value

    # -- the program's side --------------------------------------------------
    def call_begin(self):
        """An entry point's call begins (asked only while `steady_at` is
        None).  None where the call is itself being traced into somebody
        else's program."""
        if any(span[0] for span in self._thread().spans.values()):
            return None
        return time.time(), self.made

    def call_end(self, began) -> None:
        """... and ends: set-up ended where it began, if the log made no
        record meanwhile and no span is open on another thread."""
        if began is None:
            return
        with self._lock:
            if (self.steady_at is None and self.made == began[1]
                    and not self.open):
                self.steady_at = began[0]

    def reopen(self) -> None:
        """A job's set-up begins (`bps.init()`): what it compiles until
        its entry points settle again is no recompile."""
        with self._lock:
            self.steady_at = None

    def claim(self, since: int, cause: str, call=None) -> None:
        """The records this thread made from `since` (a reading of
        `made`) on were caused by `cause`, in its call number `call`."""
        me = threading.get_ident()
        with self._lock:
            for record in reversed(self.records):
                if record["seq"] < since:
                    break
                if record["thread"] == me and record["cause"] is None:
                    record["cause"] = cause
                    if call is not None:
                        record["call"] = call

    # -- what an operator reads ----------------------------------------------
    def totals(self) -> dict:
        with self._lock:
            return {"records": self.made, "kept": len(self.records),
                    "recompiles": self.recompiles,
                    "by_kind": {k: dict(v) for k, v in self.by_kind.items()},
                    "by_cache": dict(self.by_cache)}

    def snapshot(self) -> dict:
        with self._lock:
            records = [dict(r) for r in self.records]
        return {"process_start": self.process_start,
                "installed_at": self.installed_at,
                "steady_at": self.steady_at, "records": records,
                "totals": self.totals()}

    def trace_events(self, pid: int, lo_us: int, hi_us: int) -> list:
        """The records that fall inside `[lo_us, hi_us]` of the core
        tracer's clock as Chrome events on the lane `pid`, a row a kind:
        already on the clock of the spans `comm.json` holds."""
        with self._lock:
            records = [r for r in self.records
                       if r["end_us"] >= lo_us and r["start_us"] <= hi_us]
        events = []
        for r in records:
            args = {k: r[k] for k in ("nested", "cache", "retrieval_s",
                                      "cause", "call")
                    if r.get(k) is not None}
            events.append({"name": r["name"], "cat": "compile", "ph": "X",
                           "ts": r["start_us"],
                           "dur": r["end_us"] - r["start_us"], "pid": pid,
                           "tid": r["kind"], "args": args})
        return events


def _cache_answer(said: dict, seconds: float) -> str:
    if "hit" in said:
        return "hit"
    if "miss" in said:
        return "miss"
    if "asked" in said:     # sent wherever the cache is not switched off,
        import jax          # with or without a directory to keep it in
        if (jax.config.jax_compilation_cache_dir is not None and seconds
                < jax.config.jax_persistent_cache_min_compile_time_secs):
            return "small"
    return "uncached"


def _process_start():
    """When this process started, on `time.time()`'s clock: its start in
    ticks since boot (`/proc/self/stat`) against the time since boot now.
    (`/proc/stat`'s `btime` would give the boot in whole seconds.)  None
    where there is no such file."""
    try:
        with open("/proc/self/stat") as f:
            stat = f.read()
        ticks = int(stat[stat.rindex(")") + 2:].split()[19])
        since_boot = time.clock_gettime(time.CLOCK_BOOTTIME)
        return time.time() - (since_boot - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return None


def _warn_of_recompile(record: dict) -> None:
    from ..common import api, telemetry
    from ..common.logging import get_logger
    telemetry.get_registry().counter(
        "bps_recompiles_total", help=_RECOMPILES_HELP).inc()
    get_logger().warning(
        "recompile after set-up: %s compiled in %.2f s (cache: %s) at step "
        "%d; a new shape, a weak type or another placement of an argument "
        "makes a program anew (docs/troubleshooting.md)",
        record["name"], record["end"] - record["start"], record["cache"],
        api.current_step())


_RECOMPILES_HELP = ("programs compiled after set-up ended (steady_at) "
                    "with no cause of the program's own")

#: The process's log; None until `install()`.  The entry points read it
#: (and its `steady_at`, `made`) and call into it only while set-up
#: lasts or after a call of theirs that compiled.
LOG = None
_install_lock = threading.Lock()


def _on_scalar(event, value, **kwargs) -> None:
    kind = _STAGES.get(event)
    if kind is not None:
        LOG.enter(kind)


def _on_time_span(event, start, end, **kwargs) -> None:
    kind = _STAGES.get(event)
    if kind is not None:
        LOG.leave(kind, start, end, kwargs.get("fun_name"))


def _on_event(event, **kwargs) -> None:
    said = _CACHE_SAID.get(event)
    if said is not None:
        LOG.cache_said(said)


def _on_duration(event, seconds, **kwargs) -> None:
    if event == _RETRIEVAL:
        LOG.cache_said("retrieval_s", seconds)


def install() -> CompileLog:
    """The process's compile log, its listeners registered with JAX on
    the first call (`enable()` and `bps.init()` make it), and its gauges
    with the metrics registry on every call."""
    global LOG
    with _install_lock:
        if LOG is None:
            import jax.monitoring as monitoring
            LOG = CompileLog()
            monitoring.register_scalar_listener(_on_scalar)
            monitoring.register_event_time_span_listener(_on_time_span)
            monitoring.register_event_listener(_on_event)
            monitoring.register_event_duration_secs_listener(_on_duration)
    from ..common import telemetry
    reg = telemetry.get_registry()
    for kind in KINDS:
        reg.gauge("bps_compile_seconds", labels={"kind": kind},
                  help="seconds this process spent in a stage of making "
                       "programs: the union of the stage's outermost spans",
                  fn=lambda kind=kind: LOG.by_kind[kind]["seconds"])
    for answer in CACHE_ANSWERS:
        reg.gauge("bps_compile_programs", labels={"cache": answer},
                  help="programs this process compiled, by what the "
                       "persistent cache answered",
                  fn=lambda answer=answer: LOG.by_cache[answer])
    reg.counter("bps_recompiles_total", help=_RECOMPILES_HELP)
    return LOG


def snapshot() -> dict:
    """`bps.get_compile_log()`."""
    if LOG is None:
        return {"process_start": _process_start(), "installed_at": None,
                "steady_at": None, "records": [], "totals": None}
    return LOG.snapshot()


def summary() -> dict:
    """The log's totals in a line's worth: programs by the cache's
    answer and seconds by stage, for a script's closing line."""
    totals = install().totals()
    return {"programs": totals["by_cache"],
            "seconds": {kind: round(v["seconds"], 2)
                        for kind, v in totals["by_kind"].items()},
            "recompiles": totals["recompiles"]}


@contextlib.contextmanager
def caused_by(cause: str):
    """What this thread compiles inside is the program's own doing and
    no recompile: its records carry `cause`."""
    if LOG is None:
        yield
        return
    state = LOG._thread()
    before, state.cause = state.cause, cause
    try:
        yield
    finally:
        state.cause = before
