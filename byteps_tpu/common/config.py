"""Environment-variable configuration surface.

The reference framework is configured purely through environment variables
(reference: docs/env.md; parsing spread across byteps/common/global.cc:105-281,
byteps/common/communicator.cc:60-96, byteps/server/server.cc:416-448).  We keep
the same variable names so launch tooling carries over, add `BYTEPS_TPU_*`
extensions for mesh/TPU-specific knobs, and centralise every read here so the
rest of the codebase never calls os.environ directly.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

_TRUTHY = {"1", "true", "yes", "on"}


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    return int(v)


def _env_bool(name: str, default: bool = False) -> bool:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    return v.strip().lower() in _TRUTHY


def _env_str(name: str, default: str) -> str:
    v = os.environ.get(name)
    return default if v is None or v == "" else v


# Page size used for partition alignment (reference: common.h:281-285 Align()).
ALIGN_BYTES = 4096


@dataclasses.dataclass
class Config:
    """Snapshot of all knobs. Built by `Config.from_env()` at init time.

    Field names follow the reference env vars they mirror.
    """

    # ---- bootstrap / roles (reference: docs/env.md "required" section) ----
    role: str = "worker"                 # DMLC_ROLE: worker | server | scheduler | joint
    worker_id: int = 0                   # DMLC_WORKER_ID
    num_worker: int = 1                  # DMLC_NUM_WORKER
    num_server: int = 0                  # DMLC_NUM_SERVER
    scheduler_uri: str = "127.0.0.1"     # DMLC_PS_ROOT_URI
    scheduler_port: int = 9000           # DMLC_PS_ROOT_PORT
    local_rank: int = 0                  # BYTEPS_LOCAL_RANK
    local_size: int = 1                  # BYTEPS_LOCAL_SIZE
    global_rank: Optional[int] = None    # BYTEPS_GLOBAL_RANK override
    force_distributed: bool = False      # BYTEPS_FORCE_DISTRIBUTED

    # ---- communication tuning (reference: global.cc:42-43,134-144) ----
    partition_bytes: int = 4 * 1024 * 1024   # BYTEPS_PARTITION_BYTES
    min_compress_bytes: int = 65536          # BYTEPS_MIN_COMPRESS_BYTES
    # Data lanes per worker<->server pair, picked per dispatch by byte
    # credit (least-outstanding-bytes wins) so a large fused bucket can't
    # head-of-line-block small high-priority partitions.
    wire_conns: int = 4                      # BYTEPS_TPU_WIRE_CONNS
    # Colocated-server UDS fast path: when set, a server at port P also
    # listens on AF_UNIX at "<path>.P" and loopback workers dial it first
    # (bit-identical framing, TCP fallback).  Empty = TCP only.
    server_uds: str = ""                     # BYTEPS_TPU_SERVER_UDS
    # SO_SNDBUF/SO_RCVBUF on worker conns and the server accept path, in
    # KiB; 0 = kernel default (auto-tuning), the historical behavior.
    sock_buf_kb: int = 0                     # BYTEPS_TPU_SOCK_BUF_KB
    # Worker-side codec pipeline threads (the reference's COMPRESS/
    # DECOMPRESS loop threads, core_loops.cc); 0 = inline encode/decode on
    # the caller/receiver threads.
    compress_threads: int = 2                # BYTEPS_TPU_COMPRESS_THREADS
    scheduling_credit: int = 0               # BYTEPS_SCHEDULING_CREDIT (0 = off)
    # Fusion-bucket layer (common/fusion.py): leaves below this size are
    # packed into dtype-homogeneous, size-capped buckets in reverse
    # backprop order, so each bucket rides one wire key at the max member
    # priority.  0 disables fusion, restoring per-leaf / whole-tree
    # behavior byte-for-byte.
    fusion_bytes: int = 1024 * 1024          # BYTEPS_TPU_FUSION_BYTES
    # Deadline (ms) after which a streaming FusionBuffer flushes a
    # not-yet-full bucket, so straggler leaves never wait on members that
    # aren't coming.  0 = flush only when full / at end of pass.
    fusion_flush_ms: float = 5.0             # BYTEPS_TPU_FUSION_FLUSH_MS
    # Fault-tolerant PS transport (server/client.py).  reconnect_attempts=0
    # keeps the historical fail-fast contract: a dropped connection fails
    # every pending request.  >0 parks in-flight partitions, re-dials under
    # bounded exponential backoff (base reconnect_backoff_ms, jittered,
    # capped at 10s/attempt) and replays them idempotently.
    reconnect_attempts: int = 0              # BYTEPS_TPU_RECONNECT_ATTEMPTS
    reconnect_backoff_ms: float = 100.0      # BYTEPS_TPU_RECONNECT_BACKOFF_MS
    # Round-stall watchdog: with no partition completing for this many
    # seconds while work is outstanding, dump a transport snapshot and fail
    # the stuck handles loudly.  0 = disabled.
    stall_timeout_s: float = 0.0             # BYTEPS_TPU_STALL_TIMEOUT_S
    # bps.barrier() deadline; 0 = wait forever (the historical default,
    # with a periodic "still waiting" warning either way).
    barrier_timeout_s: float = 0.0           # BYTEPS_TPU_BARRIER_TIMEOUT_S
    # Elastic membership (docs/elasticity.md).  evict_timeout_s > 0 arms
    # the server-side lease scanner — a worker silent that long is
    # evicted at an epoch boundary and open rounds re-finalize against
    # the survivors — and the worker-side lease heartbeat that keeps an
    # idle-but-alive worker's lease warm.  0 (default) keeps today's
    # fail-fast/stall-watchdog semantics: a dead worker wedges rounds
    # until the watchdog or barrier timeout fails them loudly.
    evict_timeout_s: float = 0.0             # BYTEPS_TPU_EVICT_TIMEOUT_S
    # How often bps.on_membership_change()'s poller re-fetches the
    # membership view (CMD_MEMBERS).  Only runs while a callback is
    # registered — an unregistered fixed job sends no extra traffic.
    membership_poll_s: float = 2.0           # BYTEPS_TPU_MEMBERSHIP_POLL_S
    # Elastic PS server tier (docs/elasticity.md "The server half").
    # ring=True arms consistent-hash key placement (common/ring.py) on
    # workers AND servers — required for drain / scale-up / failover;
    # off (default) keeps the legacy fixed hash and a wire byte-identical
    # to pre-ring.  ring_vnodes is the virtual-node count per server
    # (placement granularity; must agree across the fleet).
    ring: bool = False                       # BYTEPS_TPU_RING
    ring_vnodes: int = 64                    # BYTEPS_TPU_RING_VNODES
    # Server failover: > 0 arms the worker-side server-lease scanner — a
    # ring member whose every connection has been down this long is
    # declared dead, the survivors adopt the next ring epoch and claim
    # its key ranges, and the open round re-pushes from gradient state.
    # Implies ring placement.  0 (default): a dead server wedges its
    # keys until the stall watchdog fails them loudly (pre-ring
    # semantics).
    server_evict_timeout_s: float = 0.0      # BYTEPS_TPU_SERVER_EVICT_TIMEOUT_S
    # Value-domain consistency auditor (docs/monitoring.md "Auditing &
    # postmortem").  audit=True makes every pull carry the server's
    # publish digest (re-verified on receipt, single-bit corruption and
    # divergent sums named within one round) and arms the CMD_AUDIT
    # last-K window cross-check.  Set the same value on servers and
    # workers; off (default) keeps the wire byte-identical to pre-audit.
    audit: bool = False                      # BYTEPS_TPU_AUDIT
    audit_window: int = 16                   # BYTEPS_TPU_AUDIT_WINDOW
    # Gradient-health monitor: sample every key's norm/absmax/NaN/Inf/
    # EF-residual every N rounds on the push and pull paths (bps_grad_*
    # gauges, bps.get_health()); non-finite values fire a structured
    # ERROR naming key/round/worker/epoch.  0 (default) = off.
    health_sample_rounds: int = 0            # BYTEPS_TPU_HEALTH_SAMPLE_ROUNDS
    # Black-box flight recorder (common/flightrec.py): bounded in-memory
    # event ring (0 disables recording) dumped into a postmortem bundle
    # by the stall watchdog / failover / auditor / atexit hooks whenever
    # postmortem_dir is set.  Empty dir (default) = no files ever.
    flightrec_events: int = 4096             # BYTEPS_TPU_FLIGHTREC_EVENTS
    postmortem_dir: str = ""                 # BYTEPS_TPU_POSTMORTEM_DIR
    server_engine_threads: int = 4           # BYTEPS_SERVER_ENGINE_THREAD
    server_enable_schedule: bool = False     # BYTEPS_SERVER_ENABLE_SCHEDULE
    enable_async: bool = False               # BYTEPS_ENABLE_ASYNC
    key_hash_fn: str = "djb2"                # BYTEPS_KEY_HASH_FN

    # ---- tracing / telemetry (reference: global.cc:113-124,712-767) ----
    trace_on: bool = False               # BYTEPS_TRACE_ON
    trace_start_step: int = 10           # BYTEPS_TRACE_START_STEP
    trace_end_step: int = 20             # BYTEPS_TRACE_END_STEP
    trace_dir: str = "./traces"          # BYTEPS_TRACE_DIR
    # Distributed-trace clock alignment: how often (seconds) the worker
    # re-estimates each PS server's clock offset over timestamped
    # CMD_PINGs while tracing is on, bounding drift across a long trace
    # window.  Offsets are also estimated at trace-enable and at each
    # server-trace fetch regardless.
    clock_sync_s: float = 30.0           # BYTEPS_TPU_CLOCK_SYNC_S
    telemetry_on: bool = True            # BYTEPS_TELEMETRY_ON
    # Debug sampling: log norm + first values of any eager-path tensor
    # whose name contains this substring, at each host-visible stage
    # (reference: BYTEPS_DEBUG_SAMPLE_TENSOR, core_loops.cc:36-66; the
    # server-side analog is BYTEPS_SERVER_DEBUG(_KEY), read by the C++
    # server directly).
    debug_sample_tensor: str = ""        # BYTEPS_DEBUG_SAMPLE_TENSOR
    # Unified metrics plane (common/telemetry.py).  metrics_port > 0 serves
    # Prometheus text format at http://<host>:<port>/metrics from a
    # background thread; metrics_log appends periodic JSONL registry
    # snapshots to the given path.  Both default off — the registry itself
    # always collects (its fast path is lock-free and O(ns)).
    metrics_port: int = 0                # BYTEPS_TPU_METRICS_PORT
    metrics_log: str = ""                # BYTEPS_TPU_METRICS_LOG
    # Size cap (MiB) on the metrics JSONL before it rotates (.1/.2 kept,
    # older dropped) — a long job's snapshot log must not grow unbounded.
    metrics_log_mb: int = 64             # BYTEPS_TPU_METRICS_LOG_MB
    # Straggler detection: warn when any worker's per-worker round position
    # (from CMD_STATS) trails the lead worker by more than this many sync
    # rounds.  0 disables the warning (the lag gauges still export).
    straggler_rounds: int = 10           # BYTEPS_TPU_STRAGGLER_ROUNDS
    # Windowed key-signal plane + continuous diagnosis (common/signals.py
    # + common/doctor.py): every window, per-key timers/metrics/value
    # verdicts join into classified KeySignal records
    # (bps.get_key_signals()) and the doctor rules run over the window
    # history (bps.get_diagnosis()).  0 = off: nothing is armed, zero
    # hot-path work, wire untouched (it never touches the wire anyway).
    signal_window_s: float = 10.0        # BYTEPS_TPU_SIGNAL_WINDOW_S
    # Window summaries kept in memory (and shipped in postmortem
    # bundles' diagnosis section) — bounds the plane's footprint.
    signal_history: int = 32             # BYTEPS_TPU_SIGNAL_HISTORY
    # Adaptive-compression tuner (common/tuner.py): each signal window,
    # wire-bound keys step toward harder codecs (raw -> onebit -> elias
    # -> qblock), compute-bound/tiny keys toward raw, unhealthy keys pin
    # raw; switches are epoch-versioned CMD_CODEC renegotiations that
    # take effect at a future round boundary on every worker atomically.
    # Off (default): no tuner is constructed and the wire is
    # byte-identical to the untuned run.  Requires the signal plane
    # (BYTEPS_TPU_SIGNAL_WINDOW_S > 0).
    tuner: bool = False                  # BYTEPS_TPU_TUNER
    # Windows a key's class must persist before the tuner switches it
    # (hysteresis — the loop must not chase one noisy window).
    tuner_hold: int = 2                  # BYTEPS_TPU_TUNER_HOLD
    # Windows a reverted (or unhealthy-pinned) key stays frozen.
    tuner_blacklist: int = 8             # BYTEPS_TPU_TUNER_BLACKLIST
    # How many rounds ahead a proposed switch's boundary is placed —
    # headroom for every worker to learn of it before crossing (the
    # server's CODEC_STALE replay covers whoever still misses it).
    tuner_margin_rounds: int = 2         # BYTEPS_TPU_TUNER_MARGIN_ROUNDS
    # Fractional per-push round-time regression (vs the pre-switch
    # baseline) that reverts a switch and blacklists the key.
    tuner_regress_frac: float = 0.2      # BYTEPS_TPU_TUNER_REGRESS_FRAC
    # Knob plane (CMD_KNOB): whether the tuner's global knob proposals
    # (fusion_bytes / compress_threads / wire_conns) ACTUATE as
    # epoch-versioned CMD_KNOB sets that land at a round boundary, or
    # stay advisory log lines (the pre-knob-plane behavior).  Only
    # worker 0's tuner proposes either way.
    knob_actuate: bool = True            # BYTEPS_TPU_KNOB_ACTUATE
    # Machine-readable per-codec cost-model table (wire_bench.py
    # --codec-sweep --json writes it; the predictive tuner seeds from
    # it).  Empty = the per-user default cache path.
    knob_cost_model: str = ""            # BYTEPS_TPU_KNOB_COST_MODEL
    # Rounds ahead a knob switch's boundary is placed (same headroom
    # law as tuner_margin_rounds; KNOB_STALE covers whoever misses it).
    knob_margin_rounds: int = 2          # BYTEPS_TPU_KNOB_MARGIN_ROUNDS
    # PS-tier autoscaler (common/autoscaler.py): each signal window,
    # worker 0 reads the per-server wire-byte rate + the doctor's open
    # findings and grows/shrinks the server ring through the existing
    # RING_JOIN / drain_server primitives.  Off (default): no loop is
    # constructed and the tier only scales when an operator acts.
    # Requires the signal plane (BYTEPS_TPU_SIGNAL_WINDOW_S > 0) and
    # the elastic ring.
    autoscale: bool = False              # BYTEPS_TPU_AUTOSCALE
    autoscale_min: int = 1               # BYTEPS_TPU_AUTOSCALE_MIN
    autoscale_max: int = 4               # BYTEPS_TPU_AUTOSCALE_MAX
    # Windows a scale pressure must persist before an action, and
    # windows every action freezes the loop after (tuner-style
    # hysteresis — one noisy window must not re-shard the tier).
    autoscale_hold: int = 2              # BYTEPS_TPU_AUTOSCALE_HOLD
    autoscale_cooldown: int = 3          # BYTEPS_TPU_AUTOSCALE_COOLDOWN
    # Per-server in-window wire MiB above which the tier grows / below
    # which it shrinks (the doctor's hot-shard finding is independent
    # up-pressure; any open finding vetoes a shrink).
    autoscale_up_mb: float = 64.0        # BYTEPS_TPU_AUTOSCALE_UP_MB
    autoscale_down_mb: float = 8.0       # BYTEPS_TPU_AUTOSCALE_DOWN_MB
    # Fleet observability plane (docs/monitoring.md "Fleet plane"):
    # each signal-window roll publishes a compact summary to the server
    # tier (CMD_WINDOW) and any endpoint serves the merged per-worker
    # view (CMD_FLEET).  Off (default): zero hot-path work, wire
    # byte-identical.  fleet_windows bounds the per-worker server ring.
    fleet: bool = False                  # BYTEPS_TPU_FLEET
    fleet_windows: int = 32              # BYTEPS_TPU_FLEET_WINDOWS
    # Device/compute-plane profiler (common/devprof.py): per-step
    # device timers, live MFU gauges, device lanes in the merged trace,
    # and the device-fallback sentinel feeding doctor rules
    # device_fallback / mfu_regression.  Off (default): trainers pay a
    # module-global None check, zero gauges, wire byte-identical.
    # device_platform is the INTENDED jax platform ("tpu"/"gpu"/...);
    # when set, the sentinel convicts any run whose backend initialized
    # as something else (the silent-CPU class, live).
    devprof: bool = False                # BYTEPS_TPU_DEVPROF
    device_platform: str = ""            # BYTEPS_TPU_DEVICE_PLATFORM

    # ---- logging ----
    log_level: str = "WARNING"           # BYTEPS_LOG_LEVEL

    # ---- TPU-native extensions (no reference equivalent) ----
    # Mesh axis sizes; 0/unset means "derive from jax.device_count()".
    mesh_dp: int = 0                     # BYTEPS_TPU_MESH_DP
    mesh_tp: int = 1                     # BYTEPS_TPU_MESH_TP
    mesh_sp: int = 1                     # BYTEPS_TPU_MESH_SP
    mesh_pp: int = 1                     # BYTEPS_TPU_MESH_PP
    mesh_ep: int = 1                     # BYTEPS_TPU_MESH_EP
    # Hierarchical reduce: devices per ICI island when spanning DCN.
    ici_size: int = 0                    # BYTEPS_TPU_ICI_SIZE (0 = all local)
    # PS parity mode: route push_pull through the host KV server tier
    # instead of XLA collectives (reference default path).
    ps_mode: bool = False                # BYTEPS_TPU_PS_MODE
    # Hierarchical reduction over the PS tier (parallel/hierarchy.py):
    # workers slice-reduce in-graph (psum/shard_map), one leader per
    # slice runs the wire push_pull, the pulled value broadcasts back —
    # per-slice wire bytes drop by the slice size.  hierarchy arms the
    # plane on workers; slice_size (chips per slice, contiguous worker
    # ids) must be set identically on workers AND servers — the server
    # counts round completion in slices under it.  Defaults off/1: flat
    # mode, wire byte-identical to pre-hierarchy.
    hierarchy: bool = False              # BYTEPS_TPU_HIERARCHY
    slice_size: int = 1                  # BYTEPS_TPU_SLICE_SIZE

    @classmethod
    def from_env(cls) -> "Config":
        gr = os.environ.get("BYTEPS_GLOBAL_RANK")
        return cls(
            role=_env_str("DMLC_ROLE", "worker"),
            worker_id=_env_int("DMLC_WORKER_ID", 0),
            num_worker=_env_int("DMLC_NUM_WORKER", 1),
            num_server=_env_int("DMLC_NUM_SERVER", 0),
            scheduler_uri=_env_str("DMLC_PS_ROOT_URI", "127.0.0.1"),
            scheduler_port=_env_int("DMLC_PS_ROOT_PORT", 9000),
            local_rank=_env_int("BYTEPS_LOCAL_RANK", 0),
            local_size=_env_int("BYTEPS_LOCAL_SIZE", 1),
            global_rank=int(gr) if gr not in (None, "") else None,
            force_distributed=_env_bool("BYTEPS_FORCE_DISTRIBUTED"),
            partition_bytes=_env_int("BYTEPS_PARTITION_BYTES", 4 * 1024 * 1024),
            min_compress_bytes=_env_int("BYTEPS_MIN_COMPRESS_BYTES", 65536),
            wire_conns=_env_int("BYTEPS_TPU_WIRE_CONNS", 4),
            server_uds=_env_str("BYTEPS_TPU_SERVER_UDS", ""),
            sock_buf_kb=_env_int("BYTEPS_TPU_SOCK_BUF_KB", 0),
            compress_threads=_env_int("BYTEPS_TPU_COMPRESS_THREADS", 2),
            scheduling_credit=_env_int("BYTEPS_SCHEDULING_CREDIT", 0),
            fusion_bytes=_env_int("BYTEPS_TPU_FUSION_BYTES", 1024 * 1024),
            fusion_flush_ms=float(
                os.environ.get("BYTEPS_TPU_FUSION_FLUSH_MS") or 5.0),
            reconnect_attempts=_env_int("BYTEPS_TPU_RECONNECT_ATTEMPTS", 0),
            reconnect_backoff_ms=float(
                os.environ.get("BYTEPS_TPU_RECONNECT_BACKOFF_MS") or 100.0),
            stall_timeout_s=float(
                os.environ.get("BYTEPS_TPU_STALL_TIMEOUT_S") or 0.0),
            barrier_timeout_s=float(
                os.environ.get("BYTEPS_TPU_BARRIER_TIMEOUT_S") or 0.0),
            evict_timeout_s=float(
                os.environ.get("BYTEPS_TPU_EVICT_TIMEOUT_S") or 0.0),
            membership_poll_s=float(
                os.environ.get("BYTEPS_TPU_MEMBERSHIP_POLL_S") or 2.0),
            ring=_env_bool("BYTEPS_TPU_RING"),
            ring_vnodes=_env_int("BYTEPS_TPU_RING_VNODES", 64),
            server_evict_timeout_s=float(
                os.environ.get("BYTEPS_TPU_SERVER_EVICT_TIMEOUT_S")
                or 0.0),
            audit=_env_bool("BYTEPS_TPU_AUDIT"),
            audit_window=_env_int("BYTEPS_TPU_AUDIT_WINDOW", 16),
            health_sample_rounds=_env_int(
                "BYTEPS_TPU_HEALTH_SAMPLE_ROUNDS", 0),
            flightrec_events=_env_int("BYTEPS_TPU_FLIGHTREC_EVENTS", 4096),
            postmortem_dir=_env_str("BYTEPS_TPU_POSTMORTEM_DIR", ""),
            server_engine_threads=_env_int("BYTEPS_SERVER_ENGINE_THREAD", 4),
            server_enable_schedule=_env_bool("BYTEPS_SERVER_ENABLE_SCHEDULE"),
            enable_async=_env_bool("BYTEPS_ENABLE_ASYNC"),
            key_hash_fn=_env_str("BYTEPS_KEY_HASH_FN", "djb2"),
            trace_on=_env_bool("BYTEPS_TRACE_ON"),
            trace_start_step=_env_int("BYTEPS_TRACE_START_STEP", 10),
            trace_end_step=_env_int("BYTEPS_TRACE_END_STEP", 20),
            trace_dir=_env_str("BYTEPS_TRACE_DIR", "./traces"),
            clock_sync_s=float(
                os.environ.get("BYTEPS_TPU_CLOCK_SYNC_S") or 30.0),
            telemetry_on=_env_bool("BYTEPS_TELEMETRY_ON", True),
            debug_sample_tensor=_env_str("BYTEPS_DEBUG_SAMPLE_TENSOR", ""),
            metrics_port=_env_int("BYTEPS_TPU_METRICS_PORT", 0),
            metrics_log=_env_str("BYTEPS_TPU_METRICS_LOG", ""),
            metrics_log_mb=_env_int("BYTEPS_TPU_METRICS_LOG_MB", 64),
            straggler_rounds=_env_int("BYTEPS_TPU_STRAGGLER_ROUNDS", 10),
            signal_window_s=float(
                os.environ.get("BYTEPS_TPU_SIGNAL_WINDOW_S") or 10.0),
            signal_history=_env_int("BYTEPS_TPU_SIGNAL_HISTORY", 32),
            tuner=_env_bool("BYTEPS_TPU_TUNER"),
            tuner_hold=_env_int("BYTEPS_TPU_TUNER_HOLD", 2),
            tuner_blacklist=_env_int("BYTEPS_TPU_TUNER_BLACKLIST", 8),
            tuner_margin_rounds=_env_int(
                "BYTEPS_TPU_TUNER_MARGIN_ROUNDS", 2),
            tuner_regress_frac=float(
                os.environ.get("BYTEPS_TPU_TUNER_REGRESS_FRAC") or 0.2),
            knob_actuate=_env_bool("BYTEPS_TPU_KNOB_ACTUATE", True),
            knob_cost_model=_env_str("BYTEPS_TPU_KNOB_COST_MODEL", ""),
            knob_margin_rounds=_env_int(
                "BYTEPS_TPU_KNOB_MARGIN_ROUNDS", 2),
            autoscale=_env_bool("BYTEPS_TPU_AUTOSCALE"),
            autoscale_min=_env_int("BYTEPS_TPU_AUTOSCALE_MIN", 1),
            autoscale_max=_env_int("BYTEPS_TPU_AUTOSCALE_MAX", 4),
            autoscale_hold=_env_int("BYTEPS_TPU_AUTOSCALE_HOLD", 2),
            autoscale_cooldown=_env_int(
                "BYTEPS_TPU_AUTOSCALE_COOLDOWN", 3),
            autoscale_up_mb=float(
                os.environ.get("BYTEPS_TPU_AUTOSCALE_UP_MB") or 64.0),
            autoscale_down_mb=float(
                os.environ.get("BYTEPS_TPU_AUTOSCALE_DOWN_MB") or 8.0),
            fleet=_env_bool("BYTEPS_TPU_FLEET"),
            fleet_windows=_env_int("BYTEPS_TPU_FLEET_WINDOWS", 32),
            devprof=_env_bool("BYTEPS_TPU_DEVPROF"),
            device_platform=_env_str("BYTEPS_TPU_DEVICE_PLATFORM", ""),
            log_level=_env_str("BYTEPS_LOG_LEVEL", "WARNING"),
            mesh_dp=_env_int("BYTEPS_TPU_MESH_DP", 0),
            mesh_tp=_env_int("BYTEPS_TPU_MESH_TP", 1),
            mesh_sp=_env_int("BYTEPS_TPU_MESH_SP", 1),
            mesh_pp=_env_int("BYTEPS_TPU_MESH_PP", 1),
            mesh_ep=_env_int("BYTEPS_TPU_MESH_EP", 1),
            ici_size=_env_int("BYTEPS_TPU_ICI_SIZE", 0),
            ps_mode=_env_bool("BYTEPS_TPU_PS_MODE"),
            hierarchy=_env_bool("BYTEPS_TPU_HIERARCHY"),
            slice_size=max(1, _env_int("BYTEPS_TPU_SLICE_SIZE", 1)),
        )


_config: Optional[Config] = None


def get_config(refresh: bool = False) -> Config:
    """Process-wide config singleton; `refresh=True` re-reads the environment
    (used by resume(), mirroring the reference re-reading DMLC_* on
    byteps_resume — operations.cc:96-119)."""
    global _config
    if _config is None or refresh:
        _config = Config.from_env()
    return _config


def align(size: int, alignment: int = ALIGN_BYTES) -> int:
    """Round `size` up to a multiple of `alignment` (reference common.h:281-285)."""
    return ((size + alignment - 1) // alignment) * alignment
