"""Horovod-compatible top-level API.

Mirrors the reference's plugin surface — init/shutdown/suspend/resume, rank/
size/local_rank/local_size, declare, push_pull(_async)/synchronize/poll,
broadcast_parameters/broadcast_optimizer_state, get_pushpull_speed
(reference: byteps/torch/__init__.py:23-28, byteps/common/__init__.py:52-139,
byteps/torch/ops.py:157-236) — re-mapped onto JAX's single-controller model:

  - a *worker* is a JAX process (host); devices a process drives are its
    "local GPUs", but unlike the reference (one process per GPU,
    communicator.cc:60-96) the intra-host tier needs no UDS/shm machinery —
    the in-jit mesh collectives cover it.
  - eager push_pull reduces across processes via a jitted collective
    (multihost_utils); inside jit, use byteps_tpu.ops.collectives /
    DistributedOptimizer, which is the hot path.

The eager path exists for API parity and for small out-of-graph tensors
(metric averaging, parameter broadcast), exactly the role the reference's
synchronous handle API plays for torch.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import threading
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import doctor as doctor_mod
from . import (devprof, flightrec, host_memory, signals, stage_spans,
               telemetry)
from .config import Config, get_config
from .logging import get_logger, set_level, set_rank
from ..core.native import get_core
from ..utils import compile_cache

PyTree = Any


@dataclasses.dataclass
class _State:
    initialized: bool = False
    config: Optional[Config] = None
    step: int = 0
    step_start_us: Optional[int] = None
    jax_dist_initialized: bool = False
    handles: Dict[int, Any] = dataclasses.field(default_factory=dict)
    lock: threading.Lock = dataclasses.field(default_factory=threading.Lock)
    ps_session: Optional[Any] = None  # PS-mode client session, when enabled
    exporter: Optional[Any] = None    # TelemetryExporter, when enabled
    trace_atexit: bool = False        # crash-flush guard registered
    comm_dir: Optional[str] = None    # where this PS worker wrote a comm.json
    # Elastic membership: the last fetched view (get_membership /
    # the on_membership_change poller), the registered callback, and the
    # poller plumbing.  size() reads the cached view, so a resize is
    # visible to the training loop without a wire fetch per step.
    membership: Optional[dict] = None
    membership_cb: Optional[Any] = None
    membership_poll_stop: Optional[Any] = None
    membership_poll_thread: Optional[Any] = None
    membership_poll_interval: float = 2.0
    # Windowed key-signal plane + doctor (BYTEPS_TPU_SIGNAL_WINDOW_S>0):
    # the SignalPlane rolls one summary per window, the DoctorEngine
    # evaluates the rules over it; the final verdict is emitted exactly
    # once (shutdown or the atexit guard, whichever runs first).
    signal_plane: Optional[Any] = None
    doctor: Optional[Any] = None
    doctor_verdict_done: bool = False
    doctor_atexit: bool = False
    # Adaptive-compression tuner (BYTEPS_TPU_TUNER=1): chained onto the
    # same window stream as the doctor; worker 0 proposes CMD_CODEC
    # switches, everyone else observes/adopts.
    tuner: Optional[Any] = None
    # PS-tier autoscaler (BYTEPS_TPU_AUTOSCALE=1): chained after the
    # doctor on the same window stream; worker 0 only.
    autoscaler: Optional[Any] = None
    # Fleet observability plane (BYTEPS_TPU_FLEET=1, PS mode): every
    # worker publishes its window summary via CMD_WINDOW; worker 0
    # additionally fetches the merged CMD_FLEET view each window and
    # runs the fleet doctor + goodput ledger over it.
    fleet_engine: Optional[Any] = None       # fleet-rule DoctorEngine (w0)
    fleet_view: Optional[dict] = None        # last merged CMD_FLEET view
    fleet_windows: Optional[list] = None     # last aligned window stream
    fleet_ledger: Optional[dict] = None      # last window's goodput ledger
    fleet_published: Optional[Any] = None    # this worker's publish ring
    # Hierarchical reduction (BYTEPS_TPU_HIERARCHY=1, PS mode): the
    # HierarchicalReducer push_pull_tree/push_pull_async route through —
    # slice-reduce in-graph, leader-only wire round, broadcast back.
    # None (default) keeps the flat path byte-identical.
    hierarchy: Optional[Any] = None


_state = _State()


def _require_init():
    if not _state.initialized:
        raise RuntimeError("byteps_tpu not initialized; call bps.init() first")


# ---------------------------------------------------------------------------
# Lifecycle (reference: operations.cc:28-119)
# ---------------------------------------------------------------------------
def _configure_cpu_collectives() -> None:
    """Cross-process collectives on the CPU platform need a collectives
    backend (gloo, compiled into jaxlib); TPU's ICI/DCN needs nothing.  Must
    run before the first backend creation.  The setting only affects CPU
    client creation, so it is applied unconditionally — platform
    autodetection may resolve to cpu without JAX_PLATFORMS ever being set.
    BYTEPS_TPU_CPU_COLLECTIVES overrides the implementation
    ("gloo" | "mpi")."""
    impl = os.environ.get("BYTEPS_TPU_CPU_COLLECTIVES", "gloo").strip()
    try:
        jax.config.update("jax_cpu_collectives_implementation", impl)
    except Exception as e:  # unknown impl name / too-old jax
        get_logger().warning("could not set cpu collectives impl %r: %s",
                             impl, e)


def _reset_jax_backends() -> None:
    """Drop cached XLA clients and the api-level topology caches
    (jax.process_count & co are memoized) so the next backend creation sees
    the *current* jax.distributed world.  This is what makes elastic resize
    possible: the reference re-runs ps-lite StartAsync against new DMLC_*
    envs (reference: operations.cc:107-119); JAX caches its client, so an
    equivalent re-init requires explicitly forgetting the old backend.

    Raises rather than warns on failure: proceeding with a stale backend
    would silently keep the old world size — wrong averages or a hang."""
    from jax._src import xla_bridge as xb
    xb._clear_backends()
    jax.clear_caches()
    from jax._src import util as _jax_util
    _jax_util.clear_all_caches()


def init(lazy: bool = True) -> None:
    """Initialize the framework.

    If the DMLC_* multi-host envs describe a JAX distributed run
    (coordinator + process id), `jax.distributed.initialize` is called so the
    process joins the global mesh — the analog of the reference's ps-lite
    StartAsync + scheduler barrier (reference: global.cc:283-297).
    """
    if _state.initialized:
        return
    # The compile log, once a process; this job's set-up begins.
    compile_cache.install().reopen()
    cfg = get_config(refresh=True)
    _state.config = cfg
    if cfg.num_worker > 1 and os.environ.get("BYTEPS_TPU_JAX_DIST", "0") == "1":
        # Multi-host: map the reference's scheduler to JAX's coordinator.
        _configure_cpu_collectives()
        jax.distributed.initialize(
            coordinator_address=f"{cfg.scheduler_uri}:{cfg.scheduler_port}",
            num_processes=cfg.num_worker,
            process_id=cfg.worker_id,
        )
        _state.jax_dist_initialized = True
    set_level(cfg.log_level)   # honor a refreshed level on init/resume
    core = get_core()
    if cfg.trace_on:
        # Honor the window from the start: with START_STEP > 0 the tracer
        # (and the traced wire flags the server records spans for) stays
        # off until mark_step enters the window — the same law mark_step
        # applies at every boundary.
        core.trace_enable(cfg.trace_start_step <= _state.step
                          <= cfg.trace_end_step)
        if not _state.trace_atexit:
            # Crash flush: a run that dies mid-window (exception, failed
            # watchdog) still leaves a usable trace file — atexit runs on
            # interpreter teardown either way, and a clean shutdown()
            # already drained the buffer so the guard is then a no-op.
            import atexit
            atexit.register(_dump_trace_on_exit)
            _state.trace_atexit = True
    if cfg.ps_mode and cfg.role == "worker":
        try:
            from ..server.client import PSSession
        except ImportError as e:
            raise RuntimeError(
                "BYTEPS_TPU_PS_MODE=1 requires the PS server tier "
                "(byteps_tpu.server.client), which is missing from this "
                "build") from e
        if jax.default_backend() != "cpu":
            # The worker stages round-sized host buffers every step and
            # frees them: from here on the process keeps freed memory,
            # so a steady round writes into pages it already has.  Not
            # on the CPU backend, where XLA's own arrays share the heap
            # (common/host_memory.py).
            host_memory.keep_freed_memory()
        _state.ps_session = PSSession.from_config(cfg)
        _state.ps_session.barrier()
        if cfg.evict_timeout_s > 0:
            # Elasticity armed: size()/averages must follow an eviction
            # even when the app never registers a callback or calls
            # get_membership() — dividing by a stale launch count would
            # silently corrupt every post-eviction gradient.  Fixed jobs
            # (timeout 0) start no poller and send no extra traffic.
            _start_membership_poller(cfg.membership_poll_s)
        if cfg.trace_on:
            # Clock alignment at trace-enable (NTP midpoint over
            # timestamped CMD_PINGs) + the periodic re-sync thread, so
            # server spans land on this worker's timeline.  An old
            # server only loses the server half of the trace.
            try:
                _state.ps_session.sync_clocks()
                _state.ps_session.start_clock_sync()
            except Exception as e:
                get_logger().warning(
                    "server clock sync unavailable (%s); trace will "
                    "carry worker spans only", e)
    if cfg.hierarchy:
        # Hierarchical reduction (docs/architecture.md "Hierarchical
        # reduction"): slice-reduce in-graph, one leader per slice on
        # the wire.  PS mode only — the in-graph collective plane
        # already composes its own hierarchy through the mesh axes.
        if _state.ps_session is None:
            get_logger().warning(
                "BYTEPS_TPU_HIERARCHY=1 outside PS mode is a no-op: "
                "the collective plane reduces intra-slice in-graph "
                "already (dp/ici_dp mesh axes) — the knob arms the PS "
                "tier's leader-aware push_pull only")
        else:
            from ..parallel import hierarchy as hierarchy_mod
            _state.hierarchy = hierarchy_mod.maybe_reducer(
                _state.ps_session)
            if _state.hierarchy is not None:
                h = _state.hierarchy
                get_logger().info(
                    "hierarchical reduction armed: slice=%d size=%d "
                    "members=%s leader=%s", h.slice_id, h.slice_size,
                    h.group.members, h.leader())
                # Misconfig check while it is still cheap to name: a
                # flat server under leader-only pushes would otherwise
                # just hang every round until the wait timeout.
                mismatch = h.verify_topology()
                if mismatch:
                    get_logger().error(
                        "hierarchical topology mismatch: %s", mismatch)
    _state.initialized = True
    # Black-box flight recorder: lifecycle events always record (bounded
    # in-memory ring, no I/O); postmortem bundles + the faulthandler
    # crash file arm only when BYTEPS_TPU_POSTMORTEM_DIR is set.  The
    # extra provider hands the bundle writer this process's cached
    # membership/step/session sections — local state only, no wire.
    flightrec.set_extra_provider(_postmortem_extra)
    flightrec.record("init", role=cfg.role, rank=rank(), size=size())
    if cfg.postmortem_dir:
        flightrec.arm_postmortem(cfg.postmortem_dir)
    if size() > 1:
        # Rank-tag the log prefix now that init() knows it: multi-worker
        # stderr interleaves indistinguishably otherwise.  Single-worker
        # runs (and everything logged before init) keep the old format.
        set_rank(rank())
    _register_builtin_collectors()
    if cfg.devprof:
        # Device plane (common/devprof.py): arm the profiler, run the
        # init-time sentinel probe (the re-probe rides every window
        # roll below), and hand the flight recorder its `device` bundle
        # section.  Off (default): none of this exists — zero gauges,
        # zero frames, the trainer hooks are a None check.
        prof = devprof.arm(intended_platform=cfg.device_platform,
                           worker=cfg.worker_id,
                           telemetry_on=cfg.telemetry_on)
        probe = prof.probe()
        if probe.get("fallback"):
            get_logger().error(
                "device sentinel convicted a fallback at init: %s",
                probe.get("reason"))
        flightrec.set_extra_provider(prof.flight_section, name="device")
    # One knob, one meaning: the plane arms iff SIGNAL_WINDOW_S > 0.
    # Deliberately NOT gated on BYTEPS_TELEMETRY_ON (which only governs
    # the throughput/step-time feeds) — a hidden second condition would
    # make "I set the window and got no doctor" undiagnosable.
    if cfg.signal_window_s > 0:
        _start_signal_plane(cfg)
    elif cfg.tuner:
        get_logger().warning(
            "BYTEPS_TPU_TUNER=1 but the signal plane is off "
            "(BYTEPS_TPU_SIGNAL_WINDOW_S=0): the tuner consumes the "
            "plane's classified windows and cannot run without it — "
            "set a window to arm the loop")
    if cfg.metrics_port > 0 or cfg.metrics_log:
        try:
            _state.exporter = telemetry.TelemetryExporter(
                telemetry.get_registry(), port=cfg.metrics_port,
                jsonl_path=cfg.metrics_log,
                max_log_mb=cfg.metrics_log_mb,
                refresh=_refresh_server_metrics,
                routes=_signal_routes()).start()
        except OSError as e:
            # A taken port / unwritable log path must not kill training —
            # the metrics plane is an observer, never a dependency.
            get_logger().error(
                "metrics exporter failed to start "
                "(BYTEPS_TPU_METRICS_PORT=%d, BYTEPS_TPU_METRICS_LOG=%r): "
                "%s — continuing without it", cfg.metrics_port,
                cfg.metrics_log, e)
            _state.exporter = None
    get_logger().info(
        "byteps_tpu initialized: role=%s rank=%d/%d local_size=%d devices=%d",
        cfg.role, rank(), size(), local_size(), jax.device_count())


def shutdown() -> None:
    if not _state.initialized:
        return
    flightrec.record("shutdown", step=_state.step)
    if _state.membership_poll_stop is not None:
        _state.membership_poll_stop.set()
        _state.membership_poll_stop = None
        _state.membership_poll_thread = None
        _state.membership_cb = None
    _state.membership = None
    # Close the signal plane's last window and emit the doctor verdict
    # BEFORE the session teardown: the final roll's CMD_STATS refresh
    # and the verdict's finding set both want the live session.
    _stop_signal_plane()
    if _state.exporter is not None:
        # Before the session teardown: the exporter's refresh hook polls
        # the live session for CMD_STATS.
        _state.exporter.stop()
        _state.exporter = None
    # Dump BEFORE the session teardown: the merged export drains the
    # server-side span ring over the live connections — and BEFORE the
    # device plane disarms, so a run that never reached its trace end
    # step still gets its device lanes in the final merged export.
    _maybe_dump_trace(final=True)
    if _state.ps_session is not None and _state.comm_dir is not None:
        # The wire's floor on this host, once a process, beside the
        # comm.json whose rounds it is read against: after the last
        # ROUND and before the lanes close (server/wire_floor.py).  A
        # run that traced nothing starts no child.
        from ..server import wire_floor
        try:
            wire_floor.probe_at_shutdown(_state.ps_session, _state.comm_dir)
        except Exception:
            get_logger().exception("wire floor probe failed")
    prof = devprof.active()
    if prof is not None:
        # Freeze the bundle's device section to the final snapshot (the
        # same static-provider law _stop_signal_plane applies): bundles
        # dumped after shutdown still answer "was it on-chip?".
        snap = prof.flight_section()
        flightrec.set_extra_provider(lambda: snap, name="device")
        devprof.disarm()
    if _state.hierarchy is not None:
        # Retire this session's SliceGroup from the process registry: a
        # re-init must meet fresh rendezvous counters (a failed round
        # can leave them desynced), while groups other in-process
        # workers hold stay untouched.
        from ..parallel.hierarchy import drop_slice_group
        drop_slice_group(_state.hierarchy.group)
    _state.hierarchy = None
    if _state.ps_session is not None:
        _state.ps_session.close()
        _state.ps_session = None
    if _state.jax_dist_initialized:
        # Required for elastic resume: a second jax.distributed.initialize
        # raises unless the first is torn down.
        jax.distributed.shutdown()
        _state.jax_dist_initialized = False
    _state.initialized = False


def suspend() -> None:
    """Elastic suspend: tear down communication, keep the registry so keys
    stay stable on resume (reference: operations.cc:96-105)."""
    shutdown()


def resume(num_workers: int, num_servers: int = 0) -> None:
    """Elastic resume with a new cluster size.  Re-reads env config and
    re-declares all tensors in original order so key assignment is unchanged
    (reference: operations.cc:107-119, global.cc:446-451).

    When the collective tier is in use (BYTEPS_TPU_JAX_DIST=1), the XLA
    backend is rebuilt for the new world size.  Device arrays created before
    suspend() belong to the old backend and must be staged through host
    memory across the resize (np.asarray before suspend, re-feed after
    resume) — the analog of the reference's requirement that tensors be
    re-declared against the new ps-lite session.
    """
    if _state.initialized:
        # resume() implies the previous session is over; make that true
        # before tearing down backends under live arrays.
        suspend()
    os.environ["DMLC_NUM_WORKER"] = str(num_workers)
    os.environ["DMLC_NUM_SERVER"] = str(num_servers)
    if os.environ.get("BYTEPS_TPU_JAX_DIST", "0") == "1":
        # Both grow and shrink need a fresh client: the cached one pins the
        # previous world's process count and gloo context.
        _reset_jax_backends()
    core = get_core()
    # The registry is preserved across suspend (the whole point); walk it so
    # any native-side rebuild keeps the original order.
    names = [core.declared_name(i) for i in range(core.num_declared())]
    init(lazy=True)
    for n in names:
        if n is not None:
            core.declare_tensor(n)


# ---------------------------------------------------------------------------
# Topology (reference: common/__init__.py:83-128)
# ---------------------------------------------------------------------------
def _env_cluster(cfg) -> bool:
    """True when the DMLC_* envs describe a multi-worker cluster that JAX's
    process topology doesn't know about (PS mode, or pre-jax.distributed
    launch): rank/size must come from the env, as the reference's do
    (reference: communicator.cc:60-96)."""
    return cfg.num_worker > 1 and not _state.jax_dist_initialized


def rank() -> int:
    cfg = _state.config or get_config()
    if cfg.global_rank is not None:
        return cfg.global_rank
    if _state.ps_session is not None or _env_cluster(cfg):
        return cfg.worker_id
    return jax.process_index()


def size() -> int:
    cfg = _state.config or get_config()
    if _state.ps_session is not None or _env_cluster(cfg):
        # Elastic membership: once the epoch has ever advanced, the world
        # is the LIVE worker set, not the launch-time DMLC_NUM_WORKER —
        # averages and per-rank sharding must rescale with it.  The view
        # is the cached one (refreshed by get_membership() and the
        # on_membership_change poller), so this stays a dict read on the
        # hot path; a fixed-membership job (epoch 0 / nothing cached)
        # keeps the launch count exactly.
        m = _state.membership
        if m is not None and int(m.get("epoch", 0)) > 0:
            return max(1, len(m.get("alive", ())))
        return cfg.num_worker
    return jax.process_count()


def local_rank() -> int:
    cfg = _state.config or get_config()
    return cfg.local_rank


def local_size() -> int:
    return jax.local_device_count()


# ---------------------------------------------------------------------------
# Declaration & keys (reference: global.cc:427-451, operations.cc:301-311)
# ---------------------------------------------------------------------------
def declare(name: str) -> int:
    """Assign (or look up) the deterministic key for a named tensor."""
    return get_core().declare_tensor(name)


def declared_key(name: str) -> int:
    return get_core().get_declared_key(name)


def register_compressor(name: str, kwargs: dict) -> int:
    """Register inter-node compression for a named tensor's PS traffic.

    The kwargs use the same strings as the reference registry
    ({"compressor": "onebit", ...}; reference: mxnet/__init__.py:236-317)
    and are shipped to the server at the tensor's INIT so it can
    decompress-sum(-recompress) (reference: operations.cc:396-408).
    Returns the declared key.  No-op outside PS mode: the collective plane
    configures compression via DistributedOptimizer instead.
    """
    _require_init()
    dk = declare(name)
    if _state.ps_session is not None:
        _state.ps_session.register_compressor(dk, kwargs)
    return dk


def get_ps_session():
    """The live PS-mode session, or None (collective mode).  Used by
    AsyncPSTrainer and power users driving the KV tier directly."""
    return _state.ps_session


# ---------------------------------------------------------------------------
# Elastic membership (docs/elasticity.md): the worker set is an
# epoch-versioned, server-negotiated table.  Joins happen implicitly (a new
# worker's init() HELLO admits it at the next epoch boundary); leaves are
# explicit (bps.leave()); evictions are lease expiries when
# BYTEPS_TPU_EVICT_TIMEOUT_S > 0.  size() follows the live set once the
# epoch has ever advanced.
# ---------------------------------------------------------------------------
def leave(drain_timeout_s: float = 60.0) -> None:
    """Gracefully exit the worker membership (PS mode).

    Drains this worker's in-flight rounds, then removes it from every
    server's membership at the next epoch boundary — survivors' open
    rounds re-finalize without it and their size() shrinks at their next
    membership refresh.  Call it before shutdown() when the departure is
    planned (autoscaler scale-down, preemption notice); an unplanned death
    is covered by lease eviction instead.  No-op outside PS mode (the
    collective plane resizes through suspend()/resume())."""
    _require_init()
    if _state.ps_session is None:
        get_logger().warning(
            "bps.leave() outside PS mode is a no-op: collective-plane "
            "resizes go through suspend()/resume()")
        return
    _state.ps_session.leave(drain_timeout_s)


def get_ring() -> dict:
    """The elastic PS server ring (CMD_RING): epoch, vnodes, member
    (id, host, port) rows, per-server keys_owned and draining flags.
    Requires PS mode with the ring armed (``BYTEPS_TPU_RING=1``);
    returns a fixed single-epoch synthetic view otherwise.  A pre-ring
    server surfaces as a clean "server too old" error, never a hang."""
    _require_init()
    sess = _state.ps_session
    if sess is None or not getattr(sess, "ring_armed", False):
        cfg = _state.config or get_config()
        n = max(1, cfg.num_server) if sess is not None else 0
        return {"epoch": 0, "armed": 0, "vnodes": cfg.ring_vnodes,
                "servers": [{"id": i} for i in range(n)]}
    return sess.get_ring()


def drain_ps_server(server_id: int, timeout_s: float = 120.0,
                    shutdown: bool = False) -> dict:
    """Gracefully scale the PS tier down by one server (CMD_DRAIN).

    The target streams every owned key's state — declared meta, merge
    store, published round, completed_round, the open round's
    contributor set — to its new consistent-hash owner, then answers
    every later frame with a redirect; sums are exact across the
    migration boundary.  Blocks until the target owns zero keys;
    ``shutdown=True`` also retires the process.  Requires PS mode with
    the ring armed (``BYTEPS_TPU_RING=1`` on workers and servers).
    Call it from ONE worker (the autoscaler's controller); the rest
    discover the new epoch through redirects and re-plan on their own.
    """
    _require_init()
    if _state.ps_session is None:
        raise RuntimeError(
            "bps.drain_ps_server() requires PS mode (BYTEPS_TPU_PS_MODE=1)")
    return _state.ps_session.drain_server(server_id, timeout_s=timeout_s,
                                          shutdown=shutdown)


def get_membership(refresh: bool = True) -> dict:
    """The current worker membership: ``{"epoch", "workers": {id:
    {"alive", "age_ms"}}, "alive": [ids], "barrier": {...}}``.

    In PS mode this is the server-negotiated epoch-versioned table
    (merged across servers); ``refresh=False`` returns the cached view
    without touching the wire.  Outside PS mode (or before the first
    fetch with refresh off) it synthesizes the fixed launch world —
    epoch 0, every rank alive.  Fetches also feed the
    ``bps_membership_epoch`` / ``bps_workers_alive`` /
    ``bps_worker_alive`` gauges."""
    _require_init()
    if _state.ps_session is not None and refresh:
        m = _state.ps_session.membership()
        _state.membership = m
        telemetry.update_membership(m)
        return m
    if _state.membership is not None:
        return _state.membership
    n = size()
    return {"epoch": 0,
            "workers": {i: {"alive": True, "age_ms": 0.0}
                        for i in range(n)},
            "alive": list(range(n)), "barrier": {}}


def _start_membership_poller(interval: float) -> None:
    """Idempotently start the CMD_MEMBERS poller: refresh the cached
    membership view (what size() reads) and the liveness gauges every
    ``interval`` seconds, and fire the registered callback on each epoch
    change.  Started by init() whenever elasticity is armed
    (BYTEPS_TPU_EVICT_TIMEOUT_S > 0) — so size() tracks an eviction even
    when no callback was registered and nothing else polls — and by
    on_membership_change() for callback users."""
    # The interval lives in _state so a later caller (e.g.
    # on_membership_change(cb, poll_s=0.2) after init() auto-started the
    # poller at the config default) retunes the LIVE poller instead of
    # being silently ignored; the loop re-reads it every cycle, so the
    # new cadence takes effect after at most one old interval.
    _state.membership_poll_interval = max(0.05, float(interval))
    if _state.membership_poll_thread is not None:
        return
    stop = threading.Event()
    _state.membership_poll_stop = stop

    def _poll():
        last_epoch = (int(_state.membership.get("epoch", 0))
                      if _state.membership else 0)
        while not stop.wait(_state.membership_poll_interval):
            sess = _state.ps_session
            if sess is None:
                return
            try:
                m = sess.membership(timeout=5.0)
            except Exception as e:
                get_logger().debug("membership poll failed: %s", e)
                continue
            _state.membership = m       # size() follows before the cb runs
            telemetry.update_membership(m)
            if int(m.get("epoch", 0)) != last_epoch:
                last_epoch = int(m.get("epoch", 0))
                flightrec.record("membership_epoch", epoch=last_epoch,
                                 alive=list(m.get("alive", ())))
                cb = _state.membership_cb
                if cb is not None:
                    try:
                        cb(m)
                    except Exception:
                        get_logger().exception(
                            "membership-change callback failed")

    t = threading.Thread(target=_poll, daemon=True,
                         name="bps-membership-poll")
    _state.membership_poll_thread = t
    t.start()


def on_membership_change(callback, poll_s: Optional[float] = None) -> None:
    """Register ``callback(membership)`` to fire when the membership
    epoch changes (join, leave, or eviction), so the training loop can
    rescale — re-derive per-rank sharding, LR scaling, data splits —
    without polling by hand.  size()/rank() already follow the new epoch
    by the time the callback runs.

    A background poller (every ``poll_s`` seconds, default
    ``BYTEPS_TPU_MEMBERSHIP_POLL_S``) re-fetches CMD_MEMBERS while a
    callback is registered — or, regardless of callbacks, while
    elasticity is armed (``BYTEPS_TPU_EVICT_TIMEOUT_S > 0``), so size()
    follows evictions either way.  ``on_membership_change(None)``
    unregisters the callback (the poller keeps running if elasticity
    armed it; otherwise it stops) — an unregistered fixed-membership job
    sends no extra wire traffic.  PS mode only."""
    _require_init()
    cfg = _state.config or get_config()
    if callback is None:
        _state.membership_cb = None
        if cfg.evict_timeout_s <= 0 and _state.membership_poll_stop \
                is not None:
            _state.membership_poll_stop.set()
            _state.membership_poll_stop = None
            _state.membership_poll_thread = None
        return
    if _state.ps_session is None:
        raise RuntimeError(
            "bps.on_membership_change() requires PS mode "
            "(BYTEPS_TPU_PS_MODE=1); the collective plane resizes "
            "through suspend()/resume()")
    _state.membership_cb = callback
    _start_membership_poller(poll_s if poll_s is not None
                             else cfg.membership_poll_s)


# ---------------------------------------------------------------------------
# Eager push_pull (reference: torch/ops.py:157-236)
# ---------------------------------------------------------------------------
def _eager_sum_across_processes(x: jax.Array) -> jax.Array:
    """True all-reduce across worker processes.

    One device per process carries the payload on a 1-D mesh; summing the
    process-sharded axis into a replicated output makes XLA emit an
    AllReduce — O(bytes) on the wire instead of the O(world*bytes) of a
    process_allgather + local sum, and one host crossing total (reference
    analog: the reference never gathers either — workers exchange exactly
    one summed copy through the PS tier, server.cc SUM_RECV).
    """
    x = jnp.asarray(x)
    devs, sharded, replicated, reduce_fn = _allreduce_plumbing(
        tuple(jax.devices()))
    shard = jax.device_put(x[None], devs[jax.process_index()])
    g = jax.make_array_from_single_device_arrays(
        (len(devs),) + x.shape, sharded, [shard])
    return jnp.asarray(reduce_fn(g).addressable_data(0))


@functools.lru_cache(maxsize=8)
def _allreduce_plumbing(all_devices: tuple):
    """Mesh + jitted sum-reduction for the eager all-reduce, cached per
    device set — a fresh lambda per call would miss jax.jit's cache (keyed
    on function identity) and retrace every eager push_pull."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    by_proc: dict = {}
    for d in all_devices:
        by_proc.setdefault(d.process_index, d)
    devs = [by_proc[i] for i in sorted(by_proc)]
    mesh = Mesh(np.array(devs), ("w",))
    sharded = NamedSharding(mesh, P("w"))
    replicated = NamedSharding(mesh, P())
    reduce_fn = jax.jit(lambda a: a.sum(axis=0), out_shardings=replicated)
    return devs, sharded, replicated, reduce_fn


def push_pull(tensor: jax.Array, name: Optional[str] = None,
              average: bool = True, priority: int = 0,
              compression=None) -> jax.Array:
    """Synchronous eager all-reduce across worker processes.

    For the in-graph hot path use DistributedOptimizer /
    ops.collectives.bucketed_tree_all_reduce instead.
    """
    h = push_pull_async(tensor, name=name, average=average, priority=priority,
                        compression=compression)
    return synchronize(h)


def push_pull_sparse(name: str, indices, rows) -> "np.ndarray":
    """Row-sparse push_pull against a declared server-resident embedding
    key (docs/sparse-embedding.md): merge this worker's ``(indices,
    rows)`` gradient into the key's open round and return the published
    rows for the same indices — wire bytes proportional to touched
    rows, never to table size.  PS mode only; most callers want the
    sharded :class:`bps.EmbeddingTable` wrapper instead, which also
    owns declaration and optimizer arming."""
    _require_init()
    if _state.ps_session is None:
        raise RuntimeError(
            "push_pull_sparse needs PS mode (the row-sparse plane is a "
            "PS-tier feature; the collective plane has no lookup tier)")
    return _state.ps_session.push_pull_sparse(declare(name), indices,
                                              rows)


def push_pull_tree(tree: PyTree, name: Optional[str] = None,
                   average: bool = True, compression=None,
                   leaf_names=None, fusion_bytes: Optional[int] = None
                   ) -> PyTree:
    """Sum/average EVERY leaf of a pytree across workers.

    The eager plugins' gradient lists ride this (reference analog: DDP
    gradient batching, torch/parallel/distributed.py:235-243; per-tensor
    eager push_pull pays one crossing per gradient).

    The tree crosses as ONE round of dispatch units.  Leaves below the
    fusion threshold (``BYTEPS_TPU_FUSION_BYTES``, default 1 MiB; the
    ``fusion_bytes`` argument overrides per call) are packed by the
    fusion planner (common/fusion.py) into dtype-homogeneous,
    size-capped buckets in reverse backprop order; each bucket rides ONE
    wire key at the max priority of its members, and larger leaves keep
    their own key and backprop-position priority.  Units go to the PS
    scheduler one by one, in (priority desc, declared key asc) order,
    each as soon as it is copied off the device — so the PS dispatcher
    sends last-layer buckets first while earlier buckets still stage
    (the overlap the priority ScheduledQueues exist for).  If a unit
    fails to stage, the units before it are already on the wire and
    complete their round unobserved; the exception surfaces, no key
    stays wedged, and the next call goes through
    (``PSSession.push_pull_group``).

    ``BYTEPS_TPU_FUSION_BYTES=0`` means NO PACKING: the plan has no
    bucket, and every leaf is a unit of the same round under its own
    key.

    Two classes of leaves are deliberately never packed; each is a unit
    of its own in the same round:
      - non-floating leaves (ints, bools): a lossy intra-node cast would
        corrupt them — they ride uncompressed and exact;
      - leaves whose `leaf_names[i]` has a PS wire compressor registered
        (register_compressor): folding them into a shared key would
        silently drop the user's compression config — they keep their own
        named key so the compressed wire still applies.
    `leaf_names` aligns with the FLATTENED leaf order (for a dict tree:
    sorted keys).  Unnamed leaves get deterministic names derived from
    the batch name + the leaf's TREE PATH (stable under structural
    growth elsewhere in the tree, unlike a flat index).

    In PS mode, inside a trace window, the call is one ``ROUND`` span
    and its stages on this thread are spans under it
    (docs/timeline.md, "The round from inside").
    """
    _require_init()
    sess = _state.ps_session
    # The compile log is told of the round only while set-up lasts: the
    # first round during which nothing is compiled ends it.
    log = compile_cache.LOG
    began = (log.call_begin()
             if log is not None and log.steady_at is None else None)
    with (sess.spans.round(name or "push_pull_tree") if sess is not None
          else stage_spans.off()):
        out = _push_pull_tree(tree, name, average, compression,
                              leaf_names, fusion_bytes)
    if began is not None:
        log.call_end(began)
    return out


def _push_pull_tree(tree, name, average, compression, leaf_names,
                    fusion_bytes):
    from .fusion import plan_buckets

    paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    if not paths_leaves:
        return tree
    leaves = [jnp.asarray(l) for _, l in paths_leaves]
    metas = [(l.shape, l.dtype, int(l.size)) for l in leaves]
    sess = _state.ps_session
    if fusion_bytes is not None:
        fb = int(fusion_bytes)
    else:
        # Knob plane: an actuated FUSION_BYTES (CMD_KNOB) overrides the
        # launch config — live_fusion_bytes() applies any staged switch
        # whose round boundary this session has reached, so every worker
        # flips to the new threshold at the same round and the
        # composition-derived bucket keys line up fleet-wide.
        fb = sess.live_fusion_bytes() if sess is not None else None
        if fb is None:
            fb = (_state.config or get_config()).fusion_bytes

    compressed_keys = set(sess._compressors) if sess is not None else set()

    def separate(i, l) -> bool:
        if not jnp.issubdtype(l.dtype, jnp.floating):
            return True
        if compressed_keys and leaf_names is not None:
            return get_core().get_declared_key(
                str(leaf_names[i])) in compressed_keys
        return False

    sep_idx = [i for i, l in enumerate(leaves) if separate(i, l)]
    batch_idx = [i for i in range(len(leaves)) if i not in set(sep_idx)]

    if name is None:
        # Key the batch by its structure + leaf signature so every worker
        # maps the same gradient set to the same declared key, and distinct
        # sets (partial backwards, several optimizers with same-shaped
        # params) get distinct keys/PS buffers.
        import hashlib
        sig = hashlib.md5(
            (str(treedef) + "|".join(f"{s}:{d}" for s, d, _ in metas))
            .encode()).hexdigest()[:12]
        name = f"byteps_tpu.tree.{sig}"

    def leaf_name(i: int) -> str:
        # Deterministic per-leaf name: explicit, or batch name + TREE PATH
        # — an unnamed push would auto-declare a FRESH key on every call
        # and grow the registry unboundedly, and an index-derived name
        # would re-key every separated leaf whenever the tree gains or
        # loses an unrelated leaf.
        if leaf_names is not None:
            return str(leaf_names[i])
        return f"{name}{jax.tree_util.keystr(paths_leaves[i][0])}"

    rnd = _Round(name, metas, average, leaf_name)
    span = rnd.span

    def plan_units(fb, only=None):
        """The dispatch units of the fusion plan under threshold `fb`,
        (unit_name, payload, priority, compression, members) where
        members = [(leaf_idx, num_elems), ...] in pack order, and their
        names; with `only`, just the units that carry one of those
        leaves."""
        plan = plan_buckets(
            tuple((i, metas[i][2], str(metas[i][1]),
                   jnp.dtype(metas[i][1]).itemsize) for i in batch_idx), fb)
        plan.record_use()
        units = []
        with span("PACK", name):
            for b in plan.buckets:
                members = [(li, n) for li, n in b.members]
                if only is not None and not any(li in only
                                                for li, _ in members):
                    continue
                packed = (jnp.concatenate(
                    [leaves[li].ravel() for li, _ in members])
                    if len(members) > 1
                    else leaves[members[0][0]].ravel())
                units.append((f"{name}.{b.tag}", packed, b.priority,
                              compression, members))
            for li, prio in plan.solo:
                if only is None or li in only:
                    units.append((leaf_name(li), leaves[li].ravel(), prio,
                                  compression, [(li, metas[li][2])]))
        return units, {u[0] for u in units}

    def replan(failed):
        # A FUSION_BYTES switch withdrew some units mid-flight: re-plan
        # the FULL fusable set under the live threshold (every worker
        # re-plans identically — the switch is global and
        # boundary-synchronized, so the new composition-derived bucket
        # keys line up fleet-wide), then re-dispatch only the units
        # carrying a withdrawn leaf.  Idempotent CMD_INIT declares the
        # new bucket keys; withdrawn handles never advanced their round,
        # so the replay stages the same round.
        live_fb = sess.live_fusion_bytes()
        return plan_units(fb if live_fb is None else live_fb, only=failed)

    units, plan_names = plan_units(fb)
    with span("PACK", name):
        for i in sep_idx:
            # Forced-solo leaves (non-float exactness, registered wire
            # compressors) join the same priority-ordered dispatch, minus
            # any lossy intra-node cast for non-floats.  Raveled like
            # every other unit: the scatter slices elements, and a 0-d
            # payload would not even be sliceable.
            comp = (compression
                    if jnp.issubdtype(metas[i][1], jnp.floating) else None)
            units.append((leaf_name(i), leaves[i].ravel(), i, comp,
                          [(i, metas[i][2])]))
    rnd.dispatch(units, plan_names)
    return jax.tree.unflatten(treedef, rnd.collect(replan))


class _Round:
    """How a set of arrays leaves this worker and comes back summed: one
    crossing of the reduction plane by dispatch units `(name, payload,
    priority, compression, members)`, members = [(leaf_idx, num_elems),
    ...] into `metas`.  `dispatch` sends them, `collect` brings them back
    as the leaves `outs`; `push_pull_tree` runs one after the other,
    `push_pull_async` returns between them.  What carries a unit is the
    PS session's `push_pull_group`; with no session, the eager
    all-reduce or, for a lone worker, nothing.
    """

    def __init__(self, name, metas, average, leaf_name=None):
        self.name, self.metas, self.leaf_name = name, metas, leaf_name
        self.average, self.sess = average, _state.ps_session
        self.hier = _state.hierarchy if self.sess is not None else None
        # The session's `RoundSpans.span`; in collective mode, no span.
        self.span = (self.sess.spans.span if self.sess is not None
                     else stage_spans.off)
        self.outs: list = [None] * len(metas)
        self.rkey = self.handles = None

    def dispatch(self, units: list, plan_names=frozenset()) -> None:
        """Order, slice-reduce, compress, declare and send `units`.
        `plan_names`: the units whose KEY IDENTITY derives from the
        fusion plan (buckets and plan solos — a different FUSION_BYTES
        re-composes them).  Registered with the session so a mid-flight
        FUSION_BYTES switch withdraws their pushes with KnobReplan
        instead of merging old-layout bytes into orphaned keys; other
        units keep layout-independent keys and replay in place."""
        from ..ops.compression import Compression
        sess, hier = self.sess, self.hier
        # The scheduler's own order, (priority desc, declared key asc),
        # with names first declared in priority order as ever.
        units.sort(key=lambda u: -u[2])
        units.sort(key=lambda u: (-u[2], declare(u[0])))
        self.units = units
        if hier is not None:
            # Hierarchical reduction: slice-reduce every unit's RAW f32
            # payload in one in-graph psum BEFORE any wire compression
            # (the leader's codec then encodes the slice sum once).
            # The rendezvous key is the unit key tuple — deterministic
            # across workers regardless of unrelated traffic.  The f32
            # cast here is NOT a new precision loss for the forced-solo
            # non-float units: the PS wire is f32 for every payload
            # (PSSession._stage casts), so flat PS mode already sums
            # them in f32 — the slice psum is the same precision class.
            self.rkey = tuple(declare(u[0]) for u in units)
            reduced = hier.reduce_payloads(
                self.rkey, [np.asarray(u[1], np.float32).ravel()
                            for u in units])
            units[:] = [(nm, jnp.asarray(red), prio, comp, members)
                        for (nm, _p, prio, comp, members), red
                        in zip(units, reduced)]
            if not hier.is_leader:
                # Followers never touch the data plane: the leader's
                # broadcast delivers the round's averaged unit outputs.
                self.skipped = sum(int(np.size(r)) * 4 for r in reduced)
                for nm, p, _, _, _ in units:
                    _debug_sample("push", nm, p)
                return
        items, self.ctxs, fusion_dks = [], [], []
        with self.span("PACK", self.name):
            for nm, payload, prio, comp, members in units:
                _debug_sample("push", nm, payload)
                comp = comp or Compression.none
                wire, ctx = comp.compress(payload)
                dk = declare(nm)
                if nm in plan_names:
                    fusion_dks.append(dk)
                if sess is not None and len(members) > 1 \
                        and get_core().trace_on:
                    # Fused bucket inside a trace window: record its
                    # member-leaf names so trace spans carry the real
                    # parameters in args.members (the analyzer's
                    # slow-bucket attribution).  Gated like every
                    # other trace feed — an untraced run must not
                    # build name lists per step.
                    sess.set_trace_members(
                        dk, [self.leaf_name(li) for li, _ in members])
                items.append((dk, wire, prio))
                self.ctxs.append((dk, comp, ctx))
        try:
            if sess is not None:
                if fusion_dks:
                    sess.note_fusion_keys(fusion_dks)
                self.handles = sess.push_pull_group(items)
            elif size() > 1 or (_state.config
                                or get_config()).force_distributed:
                # BYTEPS_FORCE_DISTRIBUTED exercises the real
                # communication path even at world size 1 — the
                # reference's test hook (reference: global.cc:149-152,
                # tests/meta_test.py:27-33).
                self.handles = [_eager_sum_across_processes(wire)
                                for _, wire, _ in items]
            else:
                self.handles = [wire for _, wire, _ in items]  # one worker
        except Exception as e:
            if hier is not None:    # as in `collect`
                hier.publish_failure(self.rkey, e)
            raise

    def done(self) -> bool:
        """Whether `collect` would find every unit arrived."""
        if self.handles is None:    # a follower: has the leader published?
            return self.hier.group.poll(self.hier.worker_id, self.rkey)
        return all(h.done() if self.sess is not None else h.is_ready()
                   for h in self.handles)

    def _scatter(self, members, vec) -> None:
        off = 0
        for li, n in members:
            shp, dt, _ = self.metas[li]
            self.outs[li] = jnp.asarray(
                vec[off:off + n]).reshape(shp).astype(dt)
            off += n

    def collect(self, replan=None) -> list:
        """Wait for the units, put them on the device, decompress,
        average and scatter them into the leaves; a slice's leader then
        hands them to its followers.  `replan(failed_leaves)` gives the
        units and plan names to dispatch again for withdrawn leaves."""
        from ..server.client import KnobReplan
        sess, hier, span = self.sess, self.hier, self.span
        if self.handles is None:
            vecs = hier.await_outs(self.rkey, skipped_bytes=self.skipped)
            for (nm, _, _, _, members), vec in zip(self.units, vecs):
                self._scatter(members, jnp.asarray(vec))
                _debug_sample("pull", nm, vec)
            # No telemetry: a follower sent nothing, and recording its
            # bytes would make the push/pull counters deny the very
            # traffic reduction the saved-bytes counter reports.
            return self.outs
        pulled_vecs = []
        for attempt in range(3):
            failed: set = set()
            replan_err = None
            try:
                for (nm, _, _, _, members), h, (dk, comp, ctx) in zip(
                        self.units, self.handles, self.ctxs):
                    try:
                        out = (_pulled_to_device(sess.spans, h, dk, nm)
                               if sess is not None else h)
                    except KnobReplan as kr:
                        if hier is not None or replan is None:
                            # The slice broadcast can't re-plan under a
                            # follower's feet — surface it like any
                            # other wire failure.
                            raise
                        failed.update(li for li, _ in members)
                        replan_err = kr
                        continue
                    with span("SCATTER", nm, key=dk):
                        out = comp.decompress(out, ctx)
                        if self.average:
                            out = out / size()
                        self._scatter(members, out)
                        _debug_sample("pull", nm, out)
                        if hier is not None:
                            pulled_vecs.append(
                                np.asarray(out, np.float32).ravel())
            except Exception as e:
                if hier is not None:
                    # Slice followers are blocked on the broadcast — a
                    # leader-side wire failure must fail the whole
                    # slice's round loudly, not strand it.
                    hier.publish_failure(self.rkey, e)
                raise
            if not failed:
                break
            if attempt == 2:
                raise replan_err
            self.dispatch(*replan(failed))
        with span("FREE", self.name):
            # The round's host memory is let go here, under a span, and
            # not at the return: the copies off the device (cached by
            # the units' arrays) and the handles' result buffers, twice
            # the tree's bytes.  Where the allocator hands them back to
            # the kernel (a worker without common/host_memory.py's
            # policy) that takes as long as some stages do.
            for owner in (self.units, self.ctxs, self.handles):
                owner.clear()
            comp = ctx = h = out = None  # the last unit's
        if hier is not None:
            hier.publish_outs(self.rkey, pulled_vecs)
        if (_state.config or get_config()).telemetry_on:
            telemetry.record_pushpull(sum(
                n * jnp.dtype(dt).itemsize for _, dt, n in self.metas))
        return self.outs


def _pulled_to_device(spans, handle, key: int, name: str) -> jax.Array:
    """Wait for a PS handle and put what it pulled on the device: the
    one place a unit's `WAIT` (written by `PSHandle.wait` itself, so
    that callers outside a round get it too) and `H2D` come from."""
    host = handle.wait()
    with spans.span("H2D", name, key=key, bytes=int(host.nbytes)):
        return jnp.asarray(host)


def _debug_sample(stage: str, name: str, tensor) -> None:
    """BYTEPS_DEBUG_SAMPLE_TENSOR: log a sample of the named tensor at a
    host-visible pipeline stage (reference: core_loops.cc:36-66 samples at
    every queue stage; here the eager path's host stages are push-entry
    and post-synchronize).  Substring match.  Written straight to stderr
    like the C++ server's BYTEPS_SERVER_DEBUG — setting the env IS the
    opt-in, independent of BYTEPS_LOG_LEVEL."""
    cfg = _state.config or get_config()
    pat = cfg.debug_sample_tensor
    if not pat or pat not in name:
        return
    import sys
    arr = np.asarray(tensor, dtype=np.float32).ravel()
    head = ", ".join(f"{v:.6g}" for v in arr[:4])
    sys.stderr.write(
        f"[byteps_tpu DEBUG_SAMPLE] {stage} name={name} "
        f"shape={tuple(np.shape(tensor))} "
        f"dtype={getattr(tensor, 'dtype', '?')} "
        f"norm2={float(np.linalg.norm(arr)):.6g} "
        f"sum={float(arr.sum()):.6g} first=[{head}]\n")
    sys.stderr.flush()


def push_pull_async(tensor: jax.Array, name: Optional[str] = None,
                    average: bool = True, priority: int = 0,
                    compression=None) -> int:
    """A round of ONE unit whose second half is deferred: the tensor is
    on its way when this returns, `synchronize` collects it."""
    _require_init()
    tensor = jnp.asarray(tensor)
    if name is None:
        name = f"byteps_tpu.tensor_{get_core().num_declared()}"
    core = get_core()
    handle = core.handle_allocate()
    t0 = core.trace_now_us()
    n = int(tensor.size)
    rnd = _Round(name, [(tensor.shape, tensor.dtype, n)], average)
    rnd.dispatch([(name, tensor.ravel(), priority, compression, [(0, n)])])
    with _state.lock:
        _state.handles[handle] = (rnd, name, t0)
    return handle


def synchronize(handle: int) -> jax.Array:
    """Block until the handle's communication completes (reference:
    torch/ops.py:222-236 spins on PollHandle; JAX gives us
    block_until_ready)."""
    with _state.lock:
        if handle not in _state.handles:
            raise ValueError(
                f"unknown or already-synchronized handle {handle}")
        rnd, name, t0 = _state.handles.pop(handle)
    out = jax.block_until_ready(rnd.collect()[0])
    core = get_core()
    core.handle_mark_done(handle)
    core.trace_record(name, "PUSH_PULL", t0, core.trace_now_us() - t0)
    core.handle_release(handle)
    return out


def poll(handle: int) -> bool:
    """True if the async op has completed: every partition pulled in PS
    mode, the buffer ready otherwise.
    Raises ValueError for a handle that was never allocated or was already
    synchronized (matching the reference's check in torch/ops.cc poll)."""
    with _state.lock:
        entry = _state.handles.get(handle)
    if entry is None:
        status = get_core().handle_poll(handle)
        if status == -1:
            raise ValueError(
                f"unknown or already-synchronized handle {handle}")
        return status == 1
    return entry[0].done()


# ---------------------------------------------------------------------------
# Broadcast (reference: torch/__init__.py:259-409 — implemented there as
# zero-non-root + push_pull sum; multihost_utils gives us the direct op)
# ---------------------------------------------------------------------------
def broadcast_parameters(params: PyTree, root_rank: int = 0) -> PyTree:
    """Make `params` identical on every worker, taking root_rank's values."""
    _require_init()
    if size() == 1:
        return params
    from jax.experimental import multihost_utils
    return multihost_utils.broadcast_one_to_all(
        params, is_source=rank() == root_rank)


def broadcast_optimizer_state(opt_state: PyTree, root_rank: int = 0) -> PyTree:
    """Optimizer-state counterpart of broadcast_parameters.  optax states are
    pytrees of arrays/scalars, so one tree broadcast covers what the reference
    does with per-scalar tensor-ization (reference: torch/__init__.py:293-409)."""
    return broadcast_parameters(opt_state, root_rank)


# ---------------------------------------------------------------------------
# Telemetry & tracing (reference: global.cc:712-767, 463-579)
# ---------------------------------------------------------------------------
def _register_builtin_collectors() -> None:
    """Attach the legacy stats surfaces to the registry as collectors.

    snapshot()/the Prometheus endpoint then export bps_codec_*,
    bps_transport_* and bps_fusion_* values that are *identical by
    construction* to get_codec_stats()/get_transport_stats()/
    get_fusion_stats() — the registry reads through the same accessors at
    snapshot time instead of keeping shadow counters that could drift.
    Idempotent (re-registering replaces the same name).
    """
    reg = telemetry.get_registry()
    # Late-bound lambdas: the accessors are defined further down this
    # module and only need to exist at snapshot time.
    reg.register_collector("codec", lambda: get_codec_stats())
    reg.register_collector("transport", lambda: get_transport_stats())
    reg.register_collector("fusion", lambda: get_fusion_stats())


_register_builtin_collectors()


def _refresh_server_metrics() -> None:
    """Exporter refresh hook: fold a fresh CMD_STATS poll into the
    registry (round-lag gauges + straggler warning) so every scrape and
    JSONL line carries scrape-fresh server state.  Quiet outside PS mode
    and while the server is unreachable — the endpoint must keep serving
    worker-side metrics even when the PS tier is the thing that broke."""
    if _state.ps_session is None:
        return
    try:
        get_server_stats()
    except Exception as e:
        get_logger().debug("CMD_STATS poll failed: %s", e)


def get_metrics() -> dict:
    """One isolated snapshot of the unified metrics registry.

    Includes every registered counter/gauge/histogram (push RTT,
    dispatcher queue wait/depth, codec encode/decode latency, step time,
    push-pull bytes, round-lag gauges) plus the collector-backed
    bps_codec_* / bps_transport_* / bps_fusion_* values, which match the
    legacy ``get_*_stats()`` accessors exactly.  Purely local — it never
    touches the network; use :func:`get_server_stats` for a live
    CMD_STATS poll.
    """
    return telemetry.get_registry().snapshot()


def get_server_stats() -> dict:
    """Live server-side stats over the wire (CMD_STATS), merged across
    servers: per-key merge counts / completed rounds / pending-pull
    depth / pushed bytes, per-worker push counts and round position, and
    server wire bytes in/out.  Also folds per-worker round lag into the
    ``bps_worker_round_lag`` gauges and logs a straggler warning for any
    worker trailing by more than ``BYTEPS_TPU_STRAGGLER_ROUNDS``.

    Returns the all-zero shape outside PS mode.  Raises a "server too
    old" RuntimeError against a pre-CMD_STATS server (the unknown
    command draws an error status, never a hang).
    """
    if _state.ps_session is None:
        return {"bytes_in": 0, "bytes_out": 0, "async": False,
                "num_workers": 0, "keys": {}, "workers": {},
                "round_lag": {}}
    cfg = _state.config or get_config()
    stats = _state.ps_session.server_stats()
    stats["round_lag"] = telemetry.update_round_lag(
        stats, cfg.straggler_rounds)
    if "members" in stats:
        # CMD_STATS carries the membership view too (epoch + per-worker
        # lease age): feed the liveness gauges so every scrape can tell
        # an evicted worker from a slow one.  Old servers omit it.
        telemetry.update_membership(
            {"epoch": stats.get("epoch", 0), "workers": stats["members"]})
    if stats.get("servers"):
        # Elastic PS ring: feed bps_ring_epoch / bps_server_alive /
        # bps_keys_owned so every scrape can tell a dead or draining
        # server from a slow one.  Old servers omit these keys.
        telemetry.update_ring(stats)
    # Server-resident optimizer plane: bps_param_version{key=} +
    # bps_opt_slot_bytes{server=}.  Quiet (no gauges registered) unless
    # some key actually runs a server-side update stage.
    telemetry.update_server_opt(stats)
    # Row-sparse embedding plane: bps_embed_rows_served_total +
    # bps_embed_table_bytes{server=}.  Quiet unless a table exists.
    telemetry.update_embed(stats)
    # Chain-replication plane: bps_repl_lag_rounds{server=} +
    # bps_repl_bytes_total.  Quiet unless BYTEPS_TPU_REPL is armed.
    telemetry.update_repl(stats)
    # Fleet observability plane: bps_fleet_windows_held{server=} +
    # bps_fleet_publishes_total.  Quiet unless BYTEPS_TPU_FLEET is
    # armed on the server tier.
    telemetry.update_fleet(stats)
    return stats


def _postmortem_extra() -> dict:
    """Bundle sections the flight recorder collects at dump time —
    strictly LOCAL state (cached membership view, step counter): a
    bundle is written exactly when the wire may be broken, so nothing
    here may block on it.  The live PSSession registers its own
    "session" provider (transport/audit/ring/health) at construction,
    so those sections ride every bundle without being computed twice."""
    out: dict = {"step": _state.step}
    if _state.membership is not None:
        out["membership"] = _state.membership
    return out


def _start_signal_plane(cfg) -> None:
    """Arm the windowed key-signal plane + doctor engine
    (``BYTEPS_TPU_SIGNAL_WINDOW_S`` > 0; docs/monitoring.md "Doctor").

    The plane is strictly local: its one optional wire touch is the
    per-window CMD_STATS refresh (PS mode, best-effort) that keeps the
    round-lag/ring gauges window-fresh — the same poll every metrics
    scrape already does.  The doctor's findings ride the log, the
    flight recorder, ``bps_doctor_findings_total`` and
    ``bps.get_diagnosis()``; postmortem bundles gain a ``diagnosis``
    section (+ the recent window history) through the flight-recorder
    provider registered here."""
    eng = doctor_mod.DoctorEngine()
    sess = _state.ps_session
    providers = {}
    if sess is not None:
        providers = {"transport": sess.transport_stats,
                     "health": sess.health_snapshot,
                     "audit": sess.audit_stats}
    prof = devprof.active()
    if prof is not None:
        # Device plane: the provider IS the window roll — it re-probes
        # the sentinel, drains the step accumulators, and updates the
        # MFU/fallback gauges; the returned section rides the summary
        # for the device_fallback / mfu_regression rules (and the fleet
        # publish doc).  Works with or without a PS session — the
        # device side has no wire dependency.
        providers["device"] = prof.window_roll

    def _refresh():
        if _state.ps_session is None:
            return None
        try:
            return get_server_stats()
        except Exception as e:
            get_logger().debug("signal window CMD_STATS poll failed: %s",
                               e)
            return None

    tuner = None
    if cfg.tuner:
        if sess is None:
            get_logger().warning(
                "BYTEPS_TPU_TUNER=1 outside PS mode: the tuner drives "
                "the PS wire codec table and has nothing to tune here")
        else:
            from . import tuner as tuner_mod
            # One proposer per job (worker 0): racing proposers would
            # converge through the server's epoch arbitration anyway,
            # but a single control loop keeps decisions explainable.
            tuner = tuner_mod.Tuner(
                sess, propose=(cfg.worker_id == 0),
                hold=cfg.tuner_hold, blacklist=cfg.tuner_blacklist,
                margin_rounds=cfg.tuner_margin_rounds,
                regress_frac=cfg.tuner_regress_frac)

    autoscaler = None
    if cfg.autoscale:
        if sess is None:
            get_logger().warning(
                "BYTEPS_TPU_AUTOSCALE=1 outside PS mode: the autoscaler "
                "drives the PS server ring and has nothing to scale here")
        elif not sess.ring_armed:
            get_logger().warning(
                "BYTEPS_TPU_AUTOSCALE=1 without the elastic ring "
                "(BYTEPS_TPU_RING=1): drain/join need ring transitions")
        elif cfg.worker_id == 0:
            # One scaler per job (worker 0, the tuner law): racing
            # scalers would propose conflicting ring transitions.
            from . import autoscaler as autoscaler_mod
            root_port = int(os.environ.get("DMLC_PS_ROOT_PORT") or 0)
            autoscaler = autoscaler_mod.Autoscaler(
                sess,
                autoscaler_mod.SubprocessExecutor(
                    root_port, num_workers=cfg.num_worker),
                min_servers=cfg.autoscale_min,
                max_servers=cfg.autoscale_max,
                hold=cfg.autoscale_hold,
                cooldown=cfg.autoscale_cooldown,
                up_mb=cfg.autoscale_up_mb,
                down_mb=cfg.autoscale_down_mb,
                doctor=eng)

    # Fleet observability plane (BYTEPS_TPU_FLEET=1, docs/monitoring.md
    # "Fleet plane"): chained onto the same window stream.  Every worker
    # publishes one compact CMD_WINDOW frame per roll; worker 0 fetches
    # the merged CMD_FLEET view, runs the fleet doctor + goodput ledger
    # over it, and — when the autoscaler is armed — feeds the scaler the
    # FLEET view instead of its own possibly-blind local one.  All of it
    # rides the window-roll thread, off the push_pull critical path.
    fleet_eng = None
    fleet_on = bool(cfg.fleet and sess is not None
                    and getattr(sess, "_fleet_wire", False))
    if fleet_on:
        import collections
        _state.fleet_published = collections.deque(
            maxlen=max(1, cfg.fleet_windows))
        if cfg.worker_id == 0:
            fleet_eng = doctor_mod.DoctorEngine(
                rules=doctor_mod.FLEET_RULES)
            _state.fleet_engine = fleet_eng

    def _fleet_pass(summary):
        from . import goodput as goodput_mod
        open_ids = [f.get("rule") for f in
                    (eng.diagnosis().get("open") or [])]
        doc = doctor_mod.fleet_publish_doc(
            summary, cfg.worker_id,
            clock=sess.fleet_clock_offset(),
            open_findings=open_ids,
            codecs=sess.codec_table())
        if sess.publish_window(int(doc.get("window") or 0), doc):
            _state.fleet_published.append(doc)
        if fleet_eng is None:
            return
        view = sess.fetch_fleet()
        _state.fleet_view = view
        fw = doctor_mod.fleet_windows_from_view(view)
        _state.fleet_windows = fw
        if not fw:
            return
        # The engine keeps its own history; feed only windows it has
        # not seen (aligned rows for OLD indexes may still gain late
        # workers, but re-observing them would reset finding identity).
        last_seen = getattr(_fleet_pass, "_last_idx", -1)
        for w in fw:
            if w["window"] > last_seen:
                fleet_eng.observe(w)
                _fleet_pass._last_idx = w["window"]
        try:
            led = goodput_mod.fleet_ledger(fw[-1])
            _state.fleet_ledger = led
            goodput_mod.update_goodput(led)
        except Exception:
            get_logger().exception("goodput ledger failed")
        if autoscaler is not None:
            fs = autoscaler_mod.fleet_summary(fw[-1])
            if fs is not None:
                autoscaler.observe(fs)

    def _on_window(summary):
        eng.observe(summary)
        if tuner is not None:
            try:
                tuner.observe(summary)
            except Exception:
                get_logger().exception("tuner window pass failed")
        if fleet_on:
            try:
                _fleet_pass(summary)
            except Exception:
                get_logger().exception("fleet window pass failed")
        if autoscaler is not None and not fleet_on:
            # Fleet-armed runs feed the scaler the merged view inside
            # _fleet_pass; unarmed runs keep the local-summary feed.
            try:
                autoscaler.observe(summary)
            except Exception:
                get_logger().exception("autoscale window pass failed")

    plane = signals.arm(window_s=cfg.signal_window_s,
                        history=cfg.signal_history,
                        refresh=_refresh, providers=providers,
                        on_window=_on_window)
    _state.signal_plane = plane
    _state.doctor = eng
    _state.tuner = tuner
    _state.autoscaler = autoscaler
    _state.doctor_verdict_done = False
    flightrec.set_extra_provider(
        lambda: {"diagnosis": eng.diagnosis(),
                 "signals": plane.history()},
        name="doctor")
    if fleet_on:
        # Postmortem bundles gain a "fleet" section: this worker's
        # published ring (the exact docs its CMD_WINDOW frames carried
        # — what fleet_view_from_bundles merges for offline parity) and,
        # on worker 0, the last merged view + fleet diagnosis.
        flightrec.set_extra_provider(_fleet_extra, name="fleet")
    if not _state.doctor_atexit:
        # Crash guard: a run that never reaches shutdown() still logs
        # its one-line verdict (and the postmortem bundle's diagnosis
        # section is dumped by flightrec's own atexit hook).
        import atexit
        atexit.register(_emit_doctor_verdict)
        _state.doctor_atexit = True


def _fleet_extra() -> dict:
    """The postmortem bundle's ``fleet`` section (strictly local state:
    a bundle dumps when the wire may be broken, so no CMD_FLEET fetch
    here — worker 0's section carries its LAST successful fetch).
    Providers merge FLAT into ``extra``, so the payload nests itself
    under the ``fleet`` key the offline readers
    (doctor.fleet_view_from_bundles, postmortem.fleet_section) expect."""
    out: dict = {"published": list(_state.fleet_published or ())}
    cfg = _state.config
    if cfg is not None:
        out["worker"] = cfg.worker_id
    if _state.fleet_view is not None:
        out["view"] = _state.fleet_view
    if _state.fleet_engine is not None:
        out["diagnosis"] = _state.fleet_engine.diagnosis()
    if _state.fleet_ledger is not None:
        out["goodput"] = _state.fleet_ledger
    return {"fleet": out}


def _emit_doctor_verdict() -> None:
    """Log the final doctor verdict exactly once per plane lifetime."""
    eng = _state.doctor
    if eng is None or _state.doctor_verdict_done:
        return
    _state.doctor_verdict_done = True
    try:
        line = eng.verdict_line()
        diag = eng.diagnosis()
        if diag.get("healthy"):
            get_logger().info(line)
        else:
            get_logger().warning(line)
    except Exception:
        pass


def _stop_signal_plane() -> None:
    if _state.signal_plane is None:
        return
    try:
        _state.signal_plane.stop(final_roll=True)   # close the last window
    except Exception:
        pass
    _emit_doctor_verdict()
    # Freeze the final diagnosis + window history into a static provider:
    # the atexit postmortem bundle (flightrec's own exit hook runs AFTER
    # shutdown) must still carry the run's verdict, or the one bundle an
    # operator actually reads would be the one missing the diagnosis.
    try:
        final = {"diagnosis": _state.doctor.diagnosis(),
                 "signals": _state.signal_plane.history()}
        flightrec.set_extra_provider(lambda: final, name="doctor")
    except Exception:
        flightrec.set_extra_provider(None, name="doctor")
    if _state.fleet_published is not None:
        # Same freeze for the fleet section: the atexit bundle must
        # still carry the published ring after the state is torn down.
        try:
            fleet_final = _fleet_extra()
            flightrec.set_extra_provider(lambda: fleet_final,
                                         name="fleet")
        except Exception:
            flightrec.set_extra_provider(None, name="fleet")
    signals.disarm()
    _state.signal_plane = None
    _state.doctor = None
    _state.tuner = None
    _state.fleet_engine = None


def _signal_routes() -> dict:
    """JSON routes for the metrics endpoint: ``/signals`` (the window
    history — what tools/bps_doctor.py polls in live mode) and
    ``/diagnosis`` (the doctor's current verdict — what the bps_top
    panel shows).  Empty when the plane is off: the endpoint then 404s
    the paths, which the consumers treat as "not armed"."""
    if _state.signal_plane is None:
        return {}
    plane, eng = _state.signal_plane, _state.doctor

    def _signals_payload():
        hist = plane.history()
        # "window" = the newest CLOSED window's index — pollers align
        # scrapes across workers by it instead of guessing from wall
        # clocks (the fleet plane's alignment key).
        return {"schema": signals.SCHEMA,
                "window_s": plane.window_s,
                "window": (hist[-1].get("window") if hist else -1),
                "windows": hist}

    routes = {"/signals": _signals_payload,
              "/diagnosis": lambda: eng.diagnosis()}
    if _state.tuner is not None:
        tuner = _state.tuner
        routes["/tuner"] = lambda: tuner.state()
    if _state.fleet_published is not None:
        routes["/fleet"] = get_fleet
    if devprof.active() is not None:
        routes["/device"] = get_device_profile
    return routes


def get_key_signals() -> dict:
    """The signal plane's last closed window: per-key ``KeySignal``
    records — wire bytes/throughput, critical-path component shares
    (queue/push_wire/serve/encode/decode), value-plane health, and the
    ``wire_bound | compute_bound | straggler_bound | tiny | unhealthy``
    classification.  The adaptive-compression tuner's input surface.
    Returns the empty shape when the plane is off
    (``BYTEPS_TPU_SIGNAL_WINDOW_S=0``)."""
    if _state.signal_plane is None:
        return {"schema": signals.SCHEMA, "armed": False, "window": -1,
                "keys": {}}
    out = _state.signal_plane.key_signals()
    out["armed"] = True
    return out


def get_diagnosis() -> dict:
    """The doctor's current verdict: open findings (severity-ranked,
    each with rule id, subject, evidence, and a playbook anchor into
    docs/troubleshooting.md), plus the recent finding history.  Returns
    ``{"armed": False, "healthy": True}`` when the plane is off."""
    if _state.doctor is None:
        return {"armed": False, "healthy": True, "open": [],
                "findings_total": 0}
    return _state.doctor.diagnosis()


def get_device_profile() -> dict:
    """The device plane's live profile (``BYTEPS_TPU_DEVPROF=1``):
    the last sentinel probe (actual vs intended platform, fallback
    conviction), lifetime and recent per-step device times
    (dispatch → ``block_until_ready``), the last window's MFU when
    ``cost_analysis()`` reports FLOPs, and the cost-analysis cache
    counters.  Served on the metrics endpoint as ``/device``.  Returns
    ``{"armed": False, ...}`` when the plane is off."""
    prof = devprof.active()
    if prof is None:
        return {"armed": False, "platform": None, "mfu": None,
                "steps_total": 0, "device_s_total": 0.0,
                "mean_step_ms": None}
    return prof.profile()


def get_step_scopes() -> Optional[dict]:
    """Which part of the model each instruction of the compiled train
    step belongs to: ``{instruction: {"scope": "mellum.moe/grouped",
    "pass": "forward" | "backward" | "recompute" | "optimizer" | "other",
    "op_name": ...}}`` for the last step ``build_train_step`` compiled, to
    lay over a ``jax.profiler`` capture, whose events carry the
    instructions' names (docs/timeline.md, "Scopes"); a kernel the
    compiler made itself, which has no path, also carries ``"lent":
    True``: its scope is what its neighbours' scopes share.  Needs no option
    and costs a step nothing; the first request reads the text of the
    executable the step holds and compiles nothing.  None where no step
    was built, or where that fails (one logged line)."""
    return devprof.get_step_scopes()


def get_compile_log() -> dict:
    """What this process traced, lowered and compiled, program by
    program, from JAX's own monitoring events
    (``utils/compile_cache.py``; ``bps.init()`` installs the listeners,
    no option): ``{"process_start", "installed_at", "steady_at",
    "records", "totals"}``.  A record is one outermost span of a stage:
    ``kind`` (``TRACE`` / ``LOWER`` / ``COMPILE``), ``name``, ``start``
    and ``end`` on ``time.time()``, ``start_us`` and ``end_us`` on the
    tracer's clock, ``thread``, ``nested`` (spans of the same stage
    inside it), ``cause`` (``train_step`` with its ``call``,
    ``scope_map``, or None) and, for a ``COMPILE``, ``cache``: ``hit``
    (with ``retrieval_s``), ``miss``, ``small`` or ``uncached``.
    ``steady_at`` is where set-up ended: the start of the first call of
    ``build_train_step``'s callable or of ``push_pull_tree`` during
    which nothing was made; a ``COMPILE`` after it is a recompile
    (``bps_recompiles_total``, one warning each).  At most 2,048 records
    are kept; ``totals`` go on counting (docs/monitoring.md)."""
    return compile_cache.snapshot()


def get_fleet() -> dict:
    """The fleet observability plane's merged view (``BYTEPS_TPU_FLEET=1``,
    PS mode): the last CMD_FLEET fetch (per-worker window rings), the
    ALIGNED window stream, the fleet doctor's verdict over it, and the
    last goodput ledger.  What ``bps_doctor --fleet`` polls live and
    the bps_top fleet panel renders.  Non-zero-worker processes publish
    but do not fetch, so they return only their own published ring;
    ``{"armed": False}`` when the plane is off."""
    if _state.fleet_published is None:
        return {"armed": False, "workers": {}, "windows": [],
                "diagnosis": {"healthy": True, "open": []}}
    out: dict = {"armed": True,
                 "published": list(_state.fleet_published),
                 "view": _state.fleet_view or {},
                 "windows": _state.fleet_windows or []}
    if _state.fleet_engine is not None:
        out["diagnosis"] = _state.fleet_engine.diagnosis()
    if _state.fleet_ledger is not None:
        out["goodput"] = _state.fleet_ledger
    return out


def get_tuner() -> dict:
    """The adaptive-compression tuner's state (``BYTEPS_TPU_TUNER=1``):
    per-key dial position / class history / blacklist state, total
    switches and reverts, and the advisory knob proposals
    (FUSION_BYTES / COMPRESS_THREADS / PARTITION_BYTES / WIRE_CONNS —
    logged, never silently applied).  ``{"armed": False}`` when the
    tuner is off."""
    if _state.tuner is None:
        return {"armed": False, "switches_total": 0, "keys": {},
                "knob_proposals": []}
    return _state.tuner.state()


def get_autoscaler() -> dict:
    """The PS-tier autoscaler's state (``BYTEPS_TPU_AUTOSCALE=1``):
    executed action records (dir/window/server), up/down totals, the
    live hysteresis streaks and cooldown horizon, and the last
    pressure-to-action detection latency.  ``{"armed": False}`` when
    the loop is off (or this worker is not worker 0)."""
    if _state.autoscaler is None:
        return {"armed": False, "actions_up": 0, "actions_down": 0,
                "actions": []}
    out = _state.autoscaler.stats()
    out["armed"] = True
    return out


def get_hierarchy() -> dict:
    """The hierarchical-reduction plane's state (``BYTEPS_TPU_HIERARCHY=1``,
    PS mode): slice topology (id/size/members), the CURRENT leader under
    the membership epoch, whether this worker is it, and the counters —
    leader vs follower wire rounds, in-graph slice reductions, and
    ``wire_bytes_saved`` (push+pull payload bytes followers never sent,
    the ``bps_hierarchy_wire_bytes_saved_total`` counter's source).
    ``{"armed": False}`` in flat mode."""
    if _state.hierarchy is None:
        return {"armed": False, "slice_size": 1, "is_leader": True,
                "leader_rounds": 0, "follower_rounds": 0,
                "intra_reduces": 0, "wire_bytes_saved": 0}
    return _state.hierarchy.snapshot()


def get_health() -> dict:
    """The gradient-health monitor's last per-key samples
    (``BYTEPS_TPU_HEALTH_SAMPLE_ROUNDS`` > 0, PS mode): ``{"sample_rounds",
    "nonfinite_total", "keys": {name: {"norm", "absmax", "nonfinite",
    "ef_residual_norm", ...}}}`` — the same values the ``bps_grad_*``
    gauges export.  The all-empty shape outside PS mode or with the
    monitor off."""
    empty = {"sample_rounds": 0, "nonfinite_total": 0, "keys": {}}
    if _state.ps_session is None:
        return empty
    return _state.ps_session.health_snapshot() or empty


def get_audit(cross_check: bool = False) -> dict:
    """The consistency auditor's verdicts (``BYTEPS_TPU_AUDIT=1``, PS
    mode; docs/monitoring.md "Auditing & postmortem").

    Default: the local counters — audited pulls checked, digest
    mismatches, lost/skewed rounds, plus the last verdict's detail.  No
    wire traffic.  ``cross_check=True`` instead fetches every server's
    CMD_AUDIT publish-digest window and compares this worker's last-K
    pulled digests against it, returning the mismatching / lost rounds
    with their contributor sets — run it (on any worker) when a
    mismatch ERROR fires or a loss curve goes sideways."""
    if _state.ps_session is None:
        return {"armed": False, "checked": 0, "mismatches": 0,
                "round_skew": 0, "unverified": 0, "last": None}
    if cross_check:
        return _state.ps_session.audit_check()
    return _state.ps_session.audit_stats()


def get_pushpull_speed() -> tuple:
    """(timestamp, MB/s) moving average, like byteps_get_pushpull_speed.

    Reimplemented on the telemetry registry: every push_pull records its
    logical tensor bytes via ``telemetry.record_pushpull``, which feeds
    both the cumulative ``bps_pushpull_bytes_total`` counter and a
    10-second moving window; this returns ``bytes_in_window / 1e6 /
    window_seconds`` — numerically equivalent to the retired native-core
    window (core.cc bps_telemetry_speed_mbps: same window length, same
    sum-over-window-divided-by-window definition), but served from the
    same registry the /metrics endpoint exports, so the two can never
    disagree.
    """
    return (time.time(), telemetry.pushpull_speed_mbps())


def get_codec_stats() -> Dict[str, int]:
    """Counters from the PS-mode codec pipeline (BYTEPS_TPU_COMPRESS_THREADS):
    parts encoded/decoded off the caller/receiver threads and the pool's
    busy time in µs.  All-zero outside PS mode or with the pipeline
    disabled (compress_threads=0) — used by tools/wire_bench.py to prove
    where codec work actually ran."""
    if _state.ps_session is not None:
        return _state.ps_session.codec_stats()
    from ..server.codec_pool import CompressionPool
    return dict(CompressionPool.ZERO_STATS)


def get_transport_stats() -> Dict[str, int]:
    """Counters from the PS transport layer.  Fault tolerance
    (BYTEPS_TPU_RECONNECT_ATTEMPTS / _STALL_TIMEOUT_S): successful
    reconnects, exhausted backoff budgets, partitions replayed (push leg /
    pull leg), partitions parked (currently / ever), and stall-watchdog
    trips.  Raw speed: receive-pool `pool_hits`/`pool_misses`/
    `pool_buffers_held`, aggregate `lane_bytes_total`/
    `lane_outstanding_bytes`, and a per-lane `lanes` row list ({server,
    lane, transport(tcp|uds), bytes_total, outstanding_bytes, sends} —
    the byte-credit scheduler's working signal), and the wire's own
    counts (`PSSession.WIRE_COUNTS`: socket calls, `push_handoffs`, the
    pushes the lanes' senders sent, and, while the tracer is on, the
    times of docs/timeline.md "What the wire waited for").  The
    get_codec_stats() analog for the transport layer; all-zero outside PS mode.  Numeric
    keys export through the metrics registry's transport collector
    (`bps_transport_*`); the `lanes` list is accessor-only."""
    if _state.ps_session is not None:
        return _state.ps_session.transport_stats()
    from ..server.client import PSSession
    # Fresh `lanes` list per call: a shallow dict() would hand every
    # caller (and the class template itself) the same mutable [].
    return {**PSSession.TRANSPORT_ZERO_STATS, "lanes": []}


def get_fusion_stats() -> Dict[str, int]:
    """Counters from the fusion-bucket layer (BYTEPS_TPU_FUSION_BYTES):
    buckets built, leaves fused vs solo, payload bytes per class, wire
    message chains saved, and streaming-flush causes (size-cap vs
    FLUSH_MS deadline vs explicit flush()/close() drain), plus the
    in-graph collective plane's plan counts.  The get_codec_stats()
    analog for fusion.  The wire-plane counters are all-zero with fusion
    disabled; `ingraph_plans`/`ingraph_buckets` track the collective
    plane's BucketPlan activity regardless (that plane packs at
    BYTEPS_PARTITION_BYTES and is not gated by the fusion knob).  Used by
    tools/wire_bench.py to prove where small tensors actually rode."""
    from .fusion import get_stats
    return get_stats()


def timeline_start_step() -> int:
    cfg = _state.config or get_config()
    return cfg.trace_start_step


def mark_step() -> None:
    """Advance the training-step counter driving the trace window
    (reference gates tracing on BYTEPS_TRACE_START/END_STEP,
    global.cc:113-124).  Within the window each step contributes a
    STEP timeline event; in-graph collective detail comes from
    jax.profiler, which this windowing composes with."""
    cfg = _state.config or get_config()
    core = get_core()
    now = core.trace_now_us()
    if cfg.trace_on and _state.step_start_us is not None \
            and cfg.trace_start_step <= _state.step <= cfg.trace_end_step:
        core.trace_record(f"step_{_state.step}", "STEP",
                          _state.step_start_us, now - _state.step_start_us)
    if cfg.telemetry_on and _state.step_start_us is not None:
        # Per-step wall time: the trace only keeps this inside its window;
        # the registry keeps the full-run distribution live.
        telemetry.get_registry().histogram(
            "bps_step_time_seconds",
            bounds=telemetry.STEP_TIME_BUCKETS,
            help="wall time between consecutive mark_step() calls"
        ).observe((now - _state.step_start_us) / 1e6)
    _state.step += 1
    _state.step_start_us = now
    if cfg.trace_on:
        core.trace_enable(cfg.trace_start_step <= _state.step
                          <= cfg.trace_end_step)
        if _state.step == cfg.trace_end_step + 1:
            _maybe_dump_trace()


def _maybe_dump_trace(final: bool = False, exiting: bool = False) -> None:
    cfg = _state.config or get_config()
    core = get_core()
    if not cfg.trace_on or core.trace_count() == 0:
        return
    d = os.path.join(cfg.trace_dir, str(local_rank()))
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, "comm.json")
    core.trace_dump(path, rank())
    _merge_server_trace(path, exiting=exiting)
    if _state.ps_session is not None:
        _state.comm_dir = d


def _dump_trace_on_exit() -> None:
    """atexit guard: flush whatever the tracer still holds (crashed or
    watchdog-failed runs never reach mark_step's window-end dump)."""
    try:
        _maybe_dump_trace(final=True, exiting=True)
    except Exception:
        pass


def _merge_server_trace(path: str, exiting: bool = False) -> None:
    """Fold server-side spans into the freshly-dumped worker trace file.

    The result is ONE Chrome/Perfetto JSON per worker with a process lane
    per host: this worker's spans on pid=rank, each PS server's
    offset-corrected spans on pid=SERVER_PID_BASE+idx (named via
    process_name metadata).  Fusion-bucket spans gain ``args.members``
    (the real parameters riding the bucket), and the file is run through
    the critical-path analyzer to feed the live
    ``bps_step_critical_path_*`` gauges.  Every step is best-effort: a
    dead server tier still leaves the plain worker trace behind.
    """
    import json
    from . import trace_analysis
    sess = _state.ps_session
    try:
        with open(path) as f:
            doc = json.load(f)
        events = doc.get("traceEvents", [])
        # tid present on metadata too: older consumers iterate e["tid"]
        # over the whole file.
        hier = _state.hierarchy
        wname = f"worker{rank()}"
        if hier is not None:
            # Per-slice lanes: the worker's process lane names its slice
            # and role, so a hierarchical trace reads as slices (leader
            # lanes carrying wire spans, follower lanes without them).
            wname += (f" slice{hier.slice_id}"
                      + (" leader" if hier.is_leader else ""))
        meta = [{"name": "process_name", "ph": "M", "pid": rank(),
                 "tid": 0, "args": {"name": wname}}]
        if sess is not None:
            core = get_core()
            try:
                # Bounded budgets everywhere: a blackholed server must
                # not pin shutdown() or a mid-training window-end dump
                # for the API-default ping+fetch budget (~80s/server).
                # Offset accuracy comes from min-RTT filtering, not
                # sample count, so the smaller ping budget costs nothing
                # on a healthy network.  The atexit (crash) path cuts
                # harder still — fail fast, keep the worker half.
                if exiting:
                    spans = sess.fetch_server_trace(
                        timeout=2.0, ping_timeout=1.0, ping_samples=2)
                else:
                    spans = sess.fetch_server_trace(
                        timeout=5.0, ping_timeout=2.0, ping_samples=3)
            except Exception as e:
                get_logger().warning("server trace unavailable: %s", e)
                spans = []
            seen_servers = set()
            for s in spans:
                dk, pidx = s["key"] >> 16, s["key"] & 0xFFFF
                nm = core.declared_name(dk) or f"key_{dk}"
                seen_servers.add(s["server"])
                args = {"key": s["key"], "round": s["round"],
                        "worker": s["worker"], "bytes": s["bytes"]}
                if hier is not None:
                    # Slice attribution on server spans: which slice's
                    # leader pushed this partition.
                    args["slice"] = s["worker"] // hier.slice_size
                events.append({
                    "name": f"{nm}.part{pidx}", "cat": "comm", "ph": "X",
                    "ts": s["ts_us"], "dur": s["dur_us"],
                    "pid": trace_analysis.SERVER_PID_BASE + s["server"],
                    "tid": s["stage"],
                    "args": args})
            for i in sorted(seen_servers):
                meta.append({"name": "process_name", "ph": "M",
                             "pid": trace_analysis.SERVER_PID_BASE + i,
                             "tid": 0, "args": {"name": f"server{i}"}})
            members = sess.trace_members()
            if members:
                for e in events:
                    k = (e.get("args") or {}).get("key")
                    if k is None:
                        continue
                    # A partition's key holds its unit's declared key
                    # above bit 16; a stage span carries the unit's own.
                    dk = k if e.get("tid") in stage_spans.STAGES else k >> 16
                    if dk in members:
                        e["args"]["members"] = members[dk]
        prof = devprof.active()
        if prof is not None:
            # Device lane (pid = DEVICE_PID_BASE + rank): the profiler's
            # step spans are stamped on the same monotonic-µs timebase
            # as the wire spans (core.trace_now_us), so they merge with
            # no offset — one timeline finally shows compute, codec,
            # and wire end to end.
            dev_events = prof.trace_events(rank())
            if dev_events:
                events.extend(dev_events)
                meta.append({
                    "name": "process_name", "ph": "M",
                    "pid": trace_analysis.DEVICE_PID_BASE + rank(),
                    "tid": 0,
                    "args": {"name": f"device{rank()} "
                             f"({(prof.profile().get('platform') or '?')}"
                             f")"}})
        log = compile_cache.LOG
        spans = [e for e in events if e.get("ph") == "X"]
        if log is not None and spans:
            # Compile lane (pid = COMPILE_PID_BASE + rank): what the
            # process traced, lowered and compiled inside the tracer's
            # window, on the tracer's clock as the device lane is: a
            # recompile lies beside the step or the round it stretched.
            pid = trace_analysis.COMPILE_PID_BASE + rank()
            made = log.trace_events(
                pid, min(e["ts"] for e in spans),
                max(e["ts"] + e.get("dur", 0) for e in spans))
            events.extend(made)
            meta.append({"name": "process_name", "ph": "M", "pid": pid,
                         "tid": 0, "args": {"name": f"compile{rank()}"}})
            meta.append({"name": "compile_log", "ph": "M", "pid": pid,
                         "tid": 0, "args": {
                             "totals": log.totals(), "spans": len(made),
                             "steady_at_us": None if log.steady_at is None
                             else int(log.steady_at * 1e6
                                      + log.offset_us)}})
        doc["traceEvents"] = meta + events
        with open(path, "w") as f:
            json.dump(doc, f)
    except Exception:
        get_logger().exception("merged trace export failed")
        return
    try:
        result = trace_analysis.analyze(doc["traceEvents"], worker=rank())
        trace_analysis.update_critical_path_gauges(result)
    except Exception:
        get_logger().exception("critical-path analysis failed")


def current_step() -> int:
    return _state.step
