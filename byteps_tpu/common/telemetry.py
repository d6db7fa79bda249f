"""Process-wide metrics plane: registry, exporters, straggler math.

BytePS's operability hinges on seeing inside the pipelined PS data path
(reference: docs/timeline.md profiles it post-hoc); this module is the
LIVE counterpart — one thread-safe registry absorbing every counter
surface the codebase grew separately (codec pool, transport, fusion,
push-pull speed) plus the hot-path signals that were measured and thrown
away (push RTT, dispatcher queue wait/depth, encode/decode latency,
per-step wall time), exported two ways:

  - a Prometheus text-format HTTP endpoint (``BYTEPS_TPU_METRICS_PORT``,
    0 = off) an operator can scrape and alert on, and
  - periodic JSONL snapshots (``BYTEPS_TPU_METRICS_LOG``) for
    offline analysis of a run with no scrape infrastructure.

Design constraints, in order:

1. **The counter fast path takes no locks.**  Counters and histograms
   stripe their state per-thread: each thread mutates only its own cell
   (a dict entry keyed by thread id), which is race-free under the GIL
   because no two threads ever write the same key.  Readers sum the
   cells.  An ``inc()`` is a dict get + int add — O(ns)-class, cheap
   enough to live inside the PS dispatcher loop (asserted by
   tests/test_telemetry.py::test_counter_fast_path_cost).
2. **Snapshots are isolated.**  ``snapshot()`` materialises plain dicts
   of plain numbers; later increments never mutate a snapshot a caller
   is holding.
3. **Legacy accessors cannot drift.**  ``bps.get_codec_stats`` /
   ``get_transport_stats`` / ``get_fusion_stats`` remain the source of
   truth for their counters; the registry pulls them through registered
   *collectors* at snapshot time, so the endpoint's values are identical
   to the legacy surfaces by construction.
"""

from __future__ import annotations

import json
import threading
import time
from bisect import bisect_left
from collections import deque
from threading import get_ident
from typing import Callable, Dict, List, Optional, Tuple

from .logging import get_logger

# ---------------------------------------------------------------------------
# Metric primitives (thread-striped, lock-free mutation)
# ---------------------------------------------------------------------------

# Default histogram bounds for latencies in SECONDS: 100µs .. 10s, log-ish.
LATENCY_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                   0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)

# Step-time bounds: 1ms .. 1h.  Real steps routinely exceed the wire
# buckets' 10s cap (the first step includes XLA compilation, large-model
# steps run minutes); capping there would collapse them all into +Inf
# and report a flat, false quantile.
STEP_TIME_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                     0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 150.0, 300.0,
                     600.0, 1800.0, 3600.0)


def _num_str(v) -> str:
    """Exact exposition rendering: ints verbatim (a %g-style format
    would round a byte counter to 6 significant digits), floats via
    repr (shortest round-trip form)."""
    if isinstance(v, int):
        return str(v)
    f = float(v)
    return str(int(f)) if f.is_integer() else repr(f)


def _esc_label(v) -> str:
    """Prometheus exposition label-value escaping: backslash, double
    quote, and newline must be escaped or a value containing them (a
    tensor name with a quote, a multi-line error string) silently
    corrupts every series after it on the scrape."""
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _label_str(labels: Optional[Dict[str, str]]) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{_esc_label(v)}"'
                     for k, v in sorted(labels.items()))
    return "{" + inner + "}"


class Counter:
    """Monotonic counter.  ``inc`` is lock-free: each thread owns one
    cell keyed by its thread id — only the owner writes it, so there is
    no write-write race to lock against; ``value()`` sums the cells
    (``list(dict.values())`` of ints runs at C level without re-entering
    Python, so it cannot observe a torn dict)."""

    __slots__ = ("name", "help", "labels", "_cells")

    def __init__(self, name: str, help: str = "",
                 labels: Optional[Dict[str, str]] = None):
        self.name = name
        self.help = help
        self.labels = dict(labels) if labels else None
        self._cells: Dict[int, int] = {}

    def inc(self, n: int = 1) -> None:
        cells = self._cells
        tid = get_ident()
        cells[tid] = cells.get(tid, 0) + n

    def value(self) -> int:
        return sum(list(self._cells.values()))


class Gauge:
    """Last-write-wins instantaneous value (a single attribute store —
    atomic under the GIL).  May also carry a callable source, sampled at
    snapshot time (for depths the owner already tracks)."""

    __slots__ = ("name", "help", "labels", "_value", "_fn")

    def __init__(self, name: str, help: str = "",
                 labels: Optional[Dict[str, str]] = None,
                 fn: Optional[Callable[[], float]] = None):
        self.name = name
        self.help = help
        self.labels = dict(labels) if labels else None
        self._value = 0.0
        self._fn = fn

    def set(self, v: float) -> None:
        self._value = v

    def set_fn(self, fn: Optional[Callable[[], float]]) -> None:
        self._fn = fn

    def value(self) -> float:
        if self._fn is not None:
            try:
                return float(self._fn())
            except Exception:
                return self._value
        return self._value


class Histogram:
    """Fixed-bucket histogram with Prometheus ``le`` (inclusive upper
    bound) semantics.  ``observe`` is lock-free via the same per-thread
    cell striping as Counter: a cell is ``[bucket_0..bucket_n, +Inf,
    sum, count]`` and only its owning thread mutates it."""

    __slots__ = ("name", "help", "labels", "bounds", "_cells")

    def __init__(self, name: str, bounds: Tuple[float, ...] = LATENCY_BUCKETS,
                 help: str = "", labels: Optional[Dict[str, str]] = None):
        self.name = name
        self.help = help
        self.labels = dict(labels) if labels else None
        self.bounds = tuple(sorted(float(b) for b in bounds))
        self._cells: Dict[int, list] = {}

    def observe(self, v: float) -> None:
        cells = self._cells
        tid = get_ident()
        cell = cells.get(tid)
        if cell is None:
            cell = cells[tid] = [0] * (len(self.bounds) + 1) + [0.0, 0]
        # bisect_left: v lands in the first bucket whose bound >= v,
        # i.e. Prometheus's inclusive `le` edge (v == bound counts in).
        cell[bisect_left(self.bounds, v)] += 1
        cell[-2] += v
        cell[-1] += 1

    def value(self) -> dict:
        """{"buckets": [(le, cumulative_count), ...], "sum", "count"}."""
        nb = len(self.bounds) + 1
        totals = [0] * nb
        s, c = 0.0, 0
        for cell in list(self._cells.values()):
            snap = list(cell)   # C-level copy: a mid-observe cell is fine
            for i in range(nb):
                totals[i] += snap[i]
            s += snap[-2]
            c += snap[-1]
        cum, buckets = 0, []
        for i, b in enumerate(self.bounds):
            cum += totals[i]
            buckets.append((b, cum))
        buckets.append((float("inf"), cum + totals[-1]))
        return {"buckets": buckets, "sum": s, "count": c}


class MovingRate:
    """Windowed byte-rate tracker — the registry reimplementation of the
    native core's telemetry window (core.cc bps_telemetry_*): events
    append lock-free (deque.append is atomic in CPython), readers prune
    and sum under a small reader-side lock."""

    def __init__(self, window_s: float = 10.0):
        self.window_s = float(window_s)
        self._events: deque = deque()
        self._read_lock = threading.Lock()

    def record(self, nbytes: int) -> None:
        self._events.append((time.monotonic(), nbytes))

    def mbps(self) -> float:
        now = time.monotonic()
        cutoff = now - self.window_s
        with self._read_lock:
            ev = self._events
            while ev and ev[0][0] < cutoff:
                ev.popleft()
            total = sum(b for _, b in list(ev))
        return (total / 1e6) / self.window_s

    def reset(self) -> None:
        with self._read_lock:
            self._events.clear()


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
class MetricsRegistry:
    """Process-wide named-metric table + collector hooks.

    Creation (`counter`/`gauge`/`histogram`) takes a lock and is
    idempotent — callers cache the returned object and mutate it
    lock-free from then on.  ``snapshot()`` renders everything, plus the
    output of every registered collector (a callable returning a flat
    ``{name: number}`` dict, e.g. the legacy ``get_codec_stats``), into
    isolated plain dicts.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: Dict[str, object] = {}   # full_name -> metric
        self._collectors: Dict[str, Callable[[], Dict[str, float]]] = {}

    # -- creation ----------------------------------------------------------
    def _get_or_make(self, cls, name, labels, factory):
        full = name + _label_str(labels)
        with self._lock:
            m = self._metrics.get(full)
            if m is None:
                m = self._metrics[full] = factory()
            elif not isinstance(m, cls):
                raise TypeError(f"metric {full!r} already registered as "
                                f"{type(m).__name__}")
            return m

    def counter(self, name: str, help: str = "",
                labels: Optional[Dict[str, str]] = None) -> Counter:
        return self._get_or_make(Counter, name, labels,
                                 lambda: Counter(name, help, labels))

    def gauge(self, name: str, help: str = "",
              labels: Optional[Dict[str, str]] = None,
              fn: Optional[Callable[[], float]] = None) -> Gauge:
        g = self._get_or_make(Gauge, name, labels,
                              lambda: Gauge(name, help, labels, fn))
        if fn is not None:
            g.set_fn(fn)
        return g

    def histogram(self, name: str,
                  bounds: Tuple[float, ...] = LATENCY_BUCKETS,
                  help: str = "",
                  labels: Optional[Dict[str, str]] = None) -> Histogram:
        h = self._get_or_make(Histogram, name, labels,
                              lambda: Histogram(name, bounds, help, labels))
        if h.bounds != tuple(sorted(float(b) for b in bounds)):
            raise ValueError(f"histogram {name!r} already registered with "
                             f"different buckets")
        return h

    def unregister(self, name: str,
                   labels: Optional[Dict[str, str]] = None) -> None:
        with self._lock:
            self._metrics.pop(name + _label_str(labels), None)

    # -- collectors --------------------------------------------------------
    def register_collector(self, name: str,
                           fn: Callable[[], Dict[str, float]]) -> None:
        """`fn()` -> flat {metric_suffix: number}; exported as gauges named
        ``bps_<name>_<suffix>``.  The legacy stats accessors ride this, so
        the endpoint can never drift from `bps.get_*_stats()`."""
        with self._lock:
            self._collectors[name] = fn

    def unregister_collector(self, name: str) -> None:
        with self._lock:
            self._collectors.pop(name, None)

    def _collect(self) -> Dict[str, float]:
        with self._lock:
            collectors = list(self._collectors.items())
        out: Dict[str, float] = {}
        for cname, fn in collectors:
            try:
                for k, v in fn().items():
                    if isinstance(v, (int, float)):
                        out[f"bps_{cname}_{k}"] = v
            except Exception:
                get_logger().exception("telemetry collector %r failed", cname)
        return out

    # -- export ------------------------------------------------------------
    def snapshot(self) -> dict:
        """Isolated plain-dict snapshot of every metric + collector."""
        with self._lock:
            metrics = list(self._metrics.values())
        out: dict = {}
        for m in metrics:
            key = m.name + _label_str(m.labels)
            out[key] = m.value()
        out.update(self._collect())
        return out

    def render_prometheus(self) -> str:
        """Prometheus text exposition format 0.0.4."""
        with self._lock:
            metrics = list(self._metrics.values())
        by_name: Dict[str, list] = {}
        for m in metrics:
            by_name.setdefault(m.name, []).append(m)
        lines: List[str] = []
        for name in sorted(by_name):
            group = by_name[name]
            first = group[0]
            if first.help:
                lines.append(f"# HELP {name} {first.help}")
            kind = ("counter" if isinstance(first, Counter)
                    else "histogram" if isinstance(first, Histogram)
                    else "gauge")
            lines.append(f"# TYPE {name} {kind}")
            for m in group:
                ls = _label_str(m.labels)
                if isinstance(m, Histogram):
                    v = m.value()
                    for le, cum in v["buckets"]:
                        le_s = "+Inf" if le == float("inf") else f"{le:g}"
                        merged = dict(m.labels or {})
                        merged["le"] = le_s
                        lines.append(
                            f"{name}_bucket{_label_str(merged)} {cum}")
                    lines.append(f"{name}_sum{ls} {_num_str(v['sum'])}")
                    lines.append(f"{name}_count{ls} {v['count']}")
                else:
                    lines.append(f"{name}{ls} {_num_str(m.value())}")
        collected = self._collect()
        for k in sorted(collected):
            lines.append(f"# TYPE {k} gauge")
            lines.append(f"{k} {_num_str(collected[k])}")
        return "\n".join(lines) + "\n"


_registry: Optional[MetricsRegistry] = None
_registry_lock = threading.Lock()

# Push-pull byte-rate window (bps.get_pushpull_speed's backing store).
_pushpull_rate = MovingRate(window_s=10.0)


def get_registry() -> MetricsRegistry:
    global _registry
    with _registry_lock:
        if _registry is None:
            _registry = MetricsRegistry()
        return _registry


def reset_registry() -> None:
    """Testing hook: drop every metric and collector (a fresh registry)."""
    global _registry
    with _registry_lock:
        _registry = MetricsRegistry()
    _pushpull_rate.reset()


def record_pushpull(nbytes: int) -> None:
    """Count one push_pull's logical bytes: feeds BOTH the cumulative
    ``bps_pushpull_bytes_total`` counter and the 10s moving-average
    window behind ``bps.get_pushpull_speed()``."""
    get_registry().counter(
        "bps_pushpull_bytes_total",
        help="logical tensor bytes pushed through push_pull").inc(nbytes)
    _pushpull_rate.record(nbytes)


def pushpull_speed_mbps() -> float:
    return _pushpull_rate.mbps()


# ---------------------------------------------------------------------------
# The form of the last call traced: static counters, one recorder
# ---------------------------------------------------------------------------
def _gauge(name: str, help: str, cast=int) -> tuple:
    # Spelled `_gauge("bps_...")` so that tools/check_metrics_docs.py,
    # which finds a registered name by that form, sees the table's.
    return name, help, cast


#: family -> keyword of `record_static` -> the gauge it sets.  A family is
#: what one call site knows when its Python body is traced into a jitted
#: step: the exchange (`ops/collectives.py` `bucketed_tree_all_reduce`),
#: the state-space scans (`models/granite_hybrid.py` and
#: `models/nemotron_h.py`, through `ops/ssd.py`), the plan of a model
#: whose layers are one part each (`models/nemotron_h.py`), a resident
#: flash-attention call's schedule
#: (`flash_attention.tile_schedule`) and a streaming one's
#: (`flash_attention.stream_schedule`; one set of gauges a `window`
#: label, "none" for a call without one, so a step with both kinds of
#: layer keeps both whatever the order they are traced in), and the
#: expert layer's grouped product (`ops/grouped_matmul.py`: which path
#: ran and on what tiles; `parallel/dropless_moe.py` adds the walk) and
#: its row moves (`ops/moe_rows.py`: one set of row counts a `use`), and
#: the attention over selected keys (`ops/sparse_attention.py`), and what
#: a model's rematerialised layers keep by name (`models/nemotron_h.py`,
#: `models/granite_hybrid.py`: one set of gauges a `name` label), and a
#: looped model's walks and the exit statistics of a batch
#: (`models/ouro.py`: `loop`, and `loop_exit` with one set of gauges a
#: `step` label).
_STATIC = {
    "ingraph_exchange": {
        "leaves": _gauge(
            "bps_ingraph_exchange_leaves",
            "non-empty leaves of the last traced in-graph exchange"),
        "groups": _gauge(
            "bps_ingraph_exchange_groups",
            "collectives it issued: one per group of leaves, or one per "
            "packed bucket"),
        # what went through slice and concatenate into flat buckets: the
        # tree's whole size when a compressor or the hierarchical
        # reduce-scatter needs buckets as vectors
        "packed_bytes": _gauge(
            "bps_ingraph_exchange_packed_bytes",
            "bytes it packed into flat buckets (0 = leaves summed in "
            "their own shapes)"),
    },
    "ssd_scan": {
        "layers": _gauge(
            "bps_ssd_scan_layers",
            "layers of the last traced step that run the chunked "
            "state-space scan"),
        "chunk": _gauge(
            "bps_ssd_chunk", "positions a chunk of that scan holds"),
        "state_bytes": _gauge(
            "bps_ssd_state_bytes",
            "bytes of chunk states ONE such layer keeps from its forward "
            "pass for its backward pass"),
        "groups": _gauge(
            "bps_ssd_scan_groups",
            "groups of heads that share one B and C in that scan: a "
            "chunk's C B^T is computed once a group"),
        "lane_block": _gauge(
            "bps_ssd_lane_block",
            "lanes of the slab of x one program of that scan's kernels "
            "holds: its block of heads' columns of [S, heads x head size]"),
        "wide_copies": _gauge(
            "bps_ssd_wide_copies",
            "transposed copies of a wide operand (x, y and their "
            "gradients) one call of that scan makes outside its kernels, "
            "both passes: 0 where the kernels read the mixer's layout, 4 "
            "in the jnp form"),
    },
    "kda_scan": {
        "layers": _gauge(
            "bps_kda_scan_layers",
            "layers of the last traced step that run the chunked delta-rule "
            "scan (ops/kda.py)"),
        "chunk": _gauge(
            "bps_kda_chunk", "positions a chunk of that scan holds"),
        "state_bytes": _gauge(
            "bps_kda_state_bytes",
            "bytes of chunk states ONE such layer keeps from its forward "
            "pass for its backward pass"),
        "kernel": _gauge(
            "bps_kda_kernel",
            "1 where the model calls that scan's Pallas form, 0 the jnp "
            "form"),
        "bwd_solve_products": _gauge(
            "bps_kda_bwd_solve_products",
            "float32 [chunk, chunk] x [chunk, chunk] products at "
            "Precision.HIGHEST in one chunk of that scan's backward kernel, "
            "counted from its traced body: 12 where the solve's gradient "
            "is two products of the inverse, 30 where autodiff walks the "
            "doubling product"),
        "heads_per_program": _gauge(
            "bps_kda_heads_per_program",
            "heads of one chunk a program of that scan's kernel `call` "
            "(`fwd`, `bwd`) holds, their equations laid out in step: the "
            "most of 4, 2, 1 (`bwd`: 2, 1) that divides the heads where a "
            "head's columns are whole tiles of 128 lanes, else 1"),
    },
    "layer_plan": {
        "stacks": _gauge(
            "bps_layer_plan_stacks",
            "stacks of leaves the last traced step of a model with "
            "one-part layers walks (models/nemotron_h.py): one a KIND of "
            "layer, whatever the order of the layers"),
        "layers": _gauge(
            "bps_layer_plan_layers",
            "layers of this kind in that step (label kind: mamba, moe, "
            "attention)"),
    },
    "flash_tiles": {
        "tiles_computed": _gauge(
            "bps_flash_tiles_computed",
            "tiles of logits one head's forward kernel computes in the "
            "last traced flash call"),
        "tiles_masked": _gauge(
            "bps_flash_tiles_masked",
            "of those, the tiles it masks: the ones the causal diagonal "
            "or a window's edge crosses"),
        "pairs_needed_share": _gauge(
            "bps_flash_pairs_needed_share",
            "(query, key) pairs the mask leaves over the pairs the "
            "computed tiles hold", float),
    },
    "flash_stream": {
        "steps": _gauge(
            "bps_flash_stream_steps",
            "grid steps one head's forward kernel walks in the last "
            "traced streaming flash call of this window"),
        "live": _gauge(
            "bps_flash_stream_live",
            "of those, the steps that compute a tile of logits"),
        "fetched": _gauge(
            "bps_flash_stream_fetched",
            "tiles of K (and as many of V) copied in: a step whose tile "
            "is the one before it copies nothing"),
    },
    "flash_block_diffusion": {
        "steps": _gauge(
            "bps_flash_bd_steps",
            "grid steps one head's forward kernel walks in the last "
            "traced flash call under a block-diffusion mask: the entries "
            "of its table, every one a live tile"),
        "live": _gauge(
            "bps_flash_bd_live",
            "of those, the steps that compute a tile of logits"),
        "fetched": _gauge(
            "bps_flash_bd_fetched",
            "tiles of K (and as many of V) copied in: a step whose tile "
            "is the one before it copies nothing"),
        "whole": _gauge(
            "bps_flash_bd_whole",
            "of the live tiles, those the mask leaves whole and the "
            "kernel does not mask"),
        "pairs_needed_share": _gauge(
            "bps_flash_bd_pairs_needed_share",
            "the (row, key) pairs the mask needs, L^2 + L beta a head, "
            "over the pairs the live tiles hold", float),
    },
    "block_diffusion_batch": {
        "masked_share": _gauge(
            "bps_bd_masked_share",
            "masked tokens over tokens in the last block-diffusion batch "
            "a caller handed `models/sdar.py` `record_batch`", float),
        "weight_mean": _gauge(
            "bps_bd_weight_mean",
            "mean over ALL tokens of that batch's loss weights, masked / "
            "t: 1 in expectation", float),
    },
    "loop": {
        "steps": _gauge(
            "bps_loop_steps",
            "walks the last traced step of a looped model makes over its "
            "layers with the same weights (models/ouro.py)"),
        "layer_applications": _gauge(
            "bps_loop_layer_applications",
            "layer applications of that step's forward pass: walks x "
            "layers held"),
        "kept_bytes": _gauge(
            "bps_loop_kept_bytes",
            "bytes of layer inputs that step's whole-layer remat keeps "
            "from the forward pass for the backward pass: applications x "
            "rows x hidden x the activations' item size", float),
    },
    "loop_exit": {
        "share": _gauge(
            "bps_exit_share",
            "mean over the tokens of the probability of leaving at walk "
            "`step` (label, from 1), in the last batch a caller handed "
            "`models/ouro.py` `record_exit`: the shares sum to 1", float),
        "nll": _gauge(
            "bps_loop_nll",
            "mean over the tokens of that batch of the head's "
            "cross-entropy after walk `step` (label, from 1), nats", float),
        "expected_steps": _gauge(
            "bps_exit_expected_steps",
            "mean over the tokens of that batch of the walk a token "
            "leaves at, sum t p_t: 1 where the gate has collapsed onto "
            "the first walk", float),
        "entropy": _gauge(
            "bps_exit_entropy",
            "mean over the tokens of that batch of the exit "
            "distribution's entropy, nats: at most ln(walks)", float),
    },
    "grouped_matmul": {
        "kernel": _gauge(
            "bps_grouped_kernel",
            "1 where the last traced grouped product of the expert layer "
            "runs the program's Pallas kernels, 0 where its shape went "
            "to lax.ragged_dot"),
        "tile_rows": _gauge(
            "bps_grouped_tile_rows", "rows of a tile of those kernels"),
        "tile_fwd_k": _gauge(
            "bps_grouped_tile_fwd_k",
            "contracted width a step of the forward kernel takes"),
        "tile_drows_n": _gauge(
            "bps_grouped_tile_drows_n",
            "contracted width a step of the rows' gradient takes"),
        "tile_dweights_k": _gauge(
            "bps_grouped_tile_dweights_k",
            "rows of a group's weight gradient one program owns"),
        "tile_dweights_n": _gauge(
            "bps_grouped_tile_dweights_n",
            "columns of it that program owns: the whole width wherever "
            "the rows can be cut"),
        "row_tiles_walked": _gauge(
            "bps_grouped_row_tiles_walked",
            "grid steps over the rows at the even routing: a tile a "
            "group's edge crosses once a group"),
        "row_tiles_needed": _gauge(
            "bps_grouped_row_tiles_needed",
            "row tiles that hold a live row at the even routing"),
        "row_tiles_buffer": _gauge(
            "bps_grouped_row_tiles_buffer",
            "row tiles of the whole buffer, padding included"),
    },
    "moe_rows": {
        "kernel": _gauge(
            "bps_moe_move_kernel",
            "1 where the last traced expert layer moves its rows with the "
            "program's Pallas kernel (ops/moe_rows.py)"),
        "tile_rows": _gauge(
            "bps_moe_move_tile_rows",
            "result rows a grid step of the last traced move takes"),
        "rows": _gauge(
            "bps_moe_move_rows",
            "(row, choice) pairs the last traced move of the use `use` "
            "(`gather`: tokens' rows into the buffer and the gradient's; "
            "`scatter`: results back to their tokens and the transpose) "
            "walks a call"),
        "texts": _gauge(
            "bps_moe_move_texts",
            "distinct bodies of that kernel traced in this process: what "
            "a run's set-up pays for, the same for four unrolled layers "
            "as for one"),
    },
    "mamba_conv": {
        "kernel": _gauge(
            "bps_mamba_conv_kernel",
            "1 where the last traced Mamba-2 mixer runs its convolution, "
            "bias and silu as the program's Pallas kernels "
            "(ops/short_conv.py mamba_conv)"),
        "rows": _gauge(
            "bps_mamba_conv_rows",
            "rows a grid step of the last traced call `call` (`fwd`, "
            "`bwd`) of those kernels takes"),
    },
    "gated_norm": {
        "kernel": _gauge(
            "bps_gated_norm_kernel",
            "1 where the last traced mixer norms stretches of its width "
            "under a gate by the program's Pallas kernels "
            "(ops/gated_norm.py gated_norm)"),
        "rows": _gauge(
            "bps_gated_norm_rows",
            "rows a grid step of the last traced call `call` (`fwd`, "
            "`bwd`) of those kernels takes"),
        "bytes": _gauge(
            "bps_gated_norm_bytes",
            "bytes the last traced call `call` has to move: x, z and y "
            "forward; x, z, dy, dx and dz backward"),
    },
    "head_norm_rope": {
        "kernel": _gauge(
            "bps_head_norm_rope_kernel",
            "1 where the last traced attention half norms and turns (or, "
            "with no scale, only turns) its queries and keys by the "
            "program's Pallas kernels (ops/head_norm_rope.py "
            "head_norm_rope)"),
        "rows": _gauge(
            "bps_head_norm_rope_rows",
            "rows a grid step of the last traced call `call` (`fwd`, "
            "`bwd`; `turn_fwd`, `turn_bwd` for the turn alone) of those "
            "kernels takes"),
        "bytes": _gauge(
            "bps_head_norm_rope_bytes",
            "bytes the last traced call `call` has to move: the heads "
            "read and written forward; the heads, their cotangent and "
            "their gradient backward (the turn alone: the cotangent and "
            "the gradient)"),
    },
    "sparse_attention": {
        "rows": _gauge(
            "bps_sparse_rows",
            "query rows of the last traced attention call over selected "
            "keys (ops/sparse_attention.py)"),
        "topk": _gauge(
            "bps_sparse_topk", "keys a row of that call selects at most"),
        "selected_pairs": _gauge(
            "bps_sparse_selected_pairs",
            "(query, key) pairs the selection leaves a sequence: the sum "
            "over its rows of min(t + 1, topk)", float),
        "visible_pairs": _gauge(
            "bps_sparse_visible_pairs",
            "pairs the causal mask alone leaves, which the indexer "
            "scores", float),
        "tiles_walked": _gauge(
            "bps_sparse_tiles_walked",
            "tiles a sequence's forward kernel computes: every causal "
            "tile, since which pairs are selected is data"),
        "index_passes": _gauge(
            "bps_sparse_index_passes",
            "of the three kernels of that call's forward and backward "
            "pass, those that compute a tile's index scores (the others "
            "read the mask as bits)"),
        "select_passes_min": _gauge(
            "bps_sparse_select_passes_min",
            "passes over its slab of scores that a block of rows of "
            "`index_topk` runs where no row has a tie at its threshold to "
            "break: one a bit of the threshold and one for the cut"),
        "select_passes_max": _gauge(
            "bps_sparse_select_passes_max",
            "the same where some row of the block has such a tie: one a "
            "bit of the threshold and one a bit of the cut's position "
            "(which of the two a block ran is in lane J + 2 of `aux`, "
            "`sparse_attention.select_passes`)"),
        "mask_bytes": _gauge(
            "bps_sparse_mask_bytes",
            "bytes of packed mask the forward kernel writes a sequence: "
            "the causal part of its words", float),
        "kept_bytes": _gauge(
            "bps_sparse_kept_bytes",
            "bytes that call names for the backward pass a sequence and "
            "layer (o, lse and the mask's words whole): what the remat "
            "policy \"selection\" keeps so that the forward kernel runs "
            "once", float),
    },
    "remat_kept": {
        "layers": _gauge(
            "bps_remat_kept_layers",
            "layers of the last traced step whose `jax.checkpoint` policy "
            "keeps the values named `name` from the forward pass "
            "(models/nemotron_h.py, models/granite_hybrid.py)"),
        "bytes": _gauge(
            "bps_remat_kept_bytes",
            "bytes those layers together hold under that name from the "
            "forward pass to the backward pass, so that the recompute "
            "does not make them again", float),
    },
    "loss_terms": {
        "weight": _gauge(
            "bps_loss_term_weight",
            "weight of the term `loss` (`main`, `mtp`) in the last traced "
            "step's loss: a model with a second prediction head adds two "
            "streamed cross-entropies a step (models/joyai.py)", float),
        "positions": _gauge(
            "bps_loss_term_positions",
            "positions that term's mean is over: the second head's last "
            "position of a sequence has no target"),
    },
}


def record_static(family: str, labels: Optional[Dict[str, str]] = None,
                  **gauges) -> None:
    """Sets the gauges of one `_STATIC` family: the form of the last
    call of its kind that was TRACED, written once per trace and not per
    step (the call sits in the Python body of a jitted step)."""
    reg = get_registry()
    for key, value in gauges.items():
        name, help, cast = _STATIC[family][key]
        reg.gauge(name, help=help, labels=labels).set(cast(value))


def record_wire_floor(result: dict) -> None:
    """`bps_wire_floor_gbps{dir=out|in|duplex}`: what server/wire_floor.py
    measured on this host, over what the session's lanes are."""
    reg = get_registry()
    for direction in ("out", "in", "duplex"):
        reg.gauge("bps_wire_floor_gbps",
                  help="GB/s this host moves between this process and "
                       "another over the session's kind and number of "
                       "lanes: the floor under the PS wire",
                  labels={"dir": direction}
                  ).set(result[direction]["GB_per_s"])


# ---------------------------------------------------------------------------
# Hierarchical reduction (parallel/hierarchy.py; BYTEPS_TPU_HIERARCHY=1)
# ---------------------------------------------------------------------------
def record_hierarchy_saved(nbytes: int,
                           registry: Optional[MetricsRegistry] = None
                           ) -> None:
    """Count push+pull payload bytes a follower did NOT send because its
    slice leader carried the round — the hierarchical plane's headline
    counter (``bps_hierarchy_wire_bytes_saved_total``).  Bytes are the
    LOGICAL f32 payload size (what the PS wire carries uncompressed);
    with a wire codec registered the on-wire saving is the codec's
    encoded size instead — smaller, same ratio."""
    (registry or get_registry()).counter(
        "bps_hierarchy_wire_bytes_saved_total",
        help="logical (uncompressed f32) push+pull payload bytes "
             "skipped by followers whose slice leader carried the "
             "wire round").inc(int(nbytes))


def update_hierarchy(slice_id: int, slice_size: int, is_leader: bool,
                     members: int,
                     registry: Optional[MetricsRegistry] = None) -> None:
    """Fold this worker's hierarchical-reduction role into the registry.

    ``bps_hierarchy_slice_size`` / ``bps_hierarchy_slice_id`` pin the
    topology; ``bps_hierarchy_is_leader`` is the 0/1 leadership gauge —
    a leadership move after an eviction is visible as the gauge flipping
    on the follower that took over.  Quiet (never registered) for flat
    runs: only an armed reducer calls this."""
    reg = registry or get_registry()
    reg.gauge("bps_hierarchy_slice_size",
              help="chips per slice (BYTEPS_TPU_SLICE_SIZE; 1 = flat)"
              ).set(int(slice_size))
    reg.gauge("bps_hierarchy_slice_id",
              help="this worker's slice id (worker_id // slice_size)"
              ).set(int(slice_id))
    reg.gauge("bps_hierarchy_slice_members",
              help="members of this worker's slice").set(int(members))
    reg.gauge("bps_hierarchy_is_leader",
              help="1 = this worker runs its slice's wire push_pull "
                   "under the current membership epoch"
              ).set(1 if is_leader else 0)


# ---------------------------------------------------------------------------
# Straggler detection (per-worker round lag from CMD_STATS)
# ---------------------------------------------------------------------------
def update_membership(membership: dict, registry: Optional[MetricsRegistry]
                      = None) -> None:
    """Fold an elastic-membership view into the registry gauges.

    ``membership`` is the merged CMD_MEMBERS shape ({"epoch", "workers":
    {id: {"alive", ...}}, ...}).  Exports ``bps_membership_epoch`` (the
    current epoch id), ``bps_workers_alive`` (live member count) and a
    per-worker ``bps_worker_alive`` 0/1 gauge — the signal bps_top and
    alerting use to tell an evicted/left worker from a merely slow one.
    A fixed-membership job exports epoch 0 and all-alive, matching its
    launch world.
    """
    reg = registry or get_registry()
    workers = membership.get("workers") or {}
    alive = membership.get("alive")
    if alive is None:
        alive = [w for w, r in workers.items() if r.get("alive")]
    reg.gauge("bps_membership_epoch",
              help="elastic membership epoch id (0 = launch set, never "
                   "resized)").set(int(membership.get("epoch", 0)))
    reg.gauge("bps_workers_alive",
              help="live workers in the current membership epoch"
              ).set(len(alive))
    for w, rec in workers.items():
        reg.gauge("bps_worker_alive",
                  help="1 = member of the current epoch, 0 = left/evicted",
                  labels={"worker": str(w)}
                  ).set(1 if rec.get("alive") else 0)


def update_ring(server_stats: dict, registry: Optional[MetricsRegistry]
                = None) -> None:
    """Fold the elastic PS-ring view from a merged CMD_STATS payload
    into the registry gauges.

    Exports ``bps_ring_epoch`` (the server-ring epoch; 0 = launch set,
    never re-sharded), ``bps_server_alive{server=}`` (1 = reachable ring
    member) and ``bps_keys_owned{server=}`` (keys whose live state the
    server holds — during a drain this runs to zero on the leaver and
    climbs on its inheritors, the migration-progress signal), plus
    ``bps_server_migrations{server=,direction=}`` counters-as-gauges for
    the in/out handoff totals.  A fixed-topology job exports epoch 0 and
    whatever its launch servers report.
    """
    reg = registry or get_registry()
    reg.gauge("bps_ring_epoch",
              help="elastic PS ring epoch (0 = launch placement, never "
                   "re-sharded)").set(int(server_stats.get("ring_epoch",
                                                           0)))
    for sid, rec in (server_stats.get("servers") or {}).items():
        lbl = {"server": str(sid)}
        reg.gauge("bps_server_alive",
                  help="1 = reachable PS ring member, 0 = dead/retired",
                  labels=lbl).set(1 if rec.get("alive") else 0)
        reg.gauge("bps_keys_owned",
                  help="keys whose live merge state this server holds",
                  labels=lbl).set(int(rec.get("keys_owned", 0)))
        for direction in ("in", "out"):
            reg.gauge("bps_server_migrations",
                      help="keys migrated across ring transitions",
                      labels={"server": str(sid), "direction": direction}
                      ).set(int(rec.get(f"migrations_{direction}", 0)))


def update_server_opt(server_stats: dict,
                      registry: Optional[MetricsRegistry] = None) -> None:
    """Fold the server-resident optimizer plane from a merged CMD_STATS
    payload into the registry gauges.

    Exports ``bps_param_version{key=}`` (published optimizer updates per
    key — a key whose completed_round grows while this stalls has a
    wedged or misconfigured update stage, doctor rule
    ``param_version_stall``) and ``bps_opt_slot_bytes{server=}`` (bytes
    of server-owned optimizer slots: params + m + v — the state that no
    longer lives N times on the workers).  Quiet for sum-only runs: no
    key carries an opt mode, so no gauge is registered and the snapshot
    is unchanged."""
    reg = registry or get_registry()
    for k, row in (server_stats.get("keys") or {}).items():
        if not isinstance(row, dict) or not int(row.get("opt_mode", 0)):
            continue
        reg.gauge("bps_param_version",
                  help="server-resident optimizer updates published for "
                       "this key (exactly one per completed round)",
                  labels={"key": str(k)}).set(
                      int(row.get("param_version", 0)))
    for sid, rec in (server_stats.get("servers") or {}).items():
        if not isinstance(rec, dict) or "opt_slot_bytes" not in rec:
            continue
        if int(rec.get("opt_slot_bytes", 0)) == 0 \
                and not int(server_stats.get("opt_updates", 0)):
            continue
        reg.gauge("bps_opt_slot_bytes",
                  help="bytes of server-owned optimizer slots "
                       "(params + m + v) held by this server",
                  labels={"server": str(sid)}).set(
                      int(rec.get("opt_slot_bytes", 0)))


def update_repl(server_stats: dict,
                registry: Optional[MetricsRegistry] = None) -> None:
    """Fold the chain-replication plane (CMD_REPL) from a merged
    CMD_STATS payload into the registry.

    Exports ``bps_repl_lag_rounds{server=}`` (how many published rounds
    the server's ring successor has not yet acked — the width of the
    would-be loss window a failover closes, and what the doctor's
    ``replication_lag`` rule watches) and ``bps_repl_bytes_total``
    (replica bytes shipped tier-wide).  Quiet when replication is
    unarmed (BYTEPS_TPU_REPL unset): no gauge is registered and the
    snapshot is unchanged — the zero-overhead-when-off law every plane
    here follows."""
    reg = registry or get_registry()
    if not server_stats.get("repl_armed"):
        return
    reg.gauge("bps_repl_bytes_total",
              help="replica bytes shipped to ring successors "
                   "(CMD_REPL), tier-wide").set(
                  int(server_stats.get("repl_bytes_total", 0)))
    for sid, rec in (server_stats.get("servers") or {}).items():
        if not isinstance(rec, dict) or "repl_lag_rounds" not in rec:
            continue
        reg.gauge("bps_repl_lag_rounds",
                  help="published rounds this server's ring successor "
                       "has not yet acked (0 = every published round "
                       "survives an owner SIGKILL)",
                  labels={"server": str(sid)}).set(
                      int(rec.get("repl_lag_rounds", 0)))


def update_fleet(server_stats: dict,
                 registry: Optional[MetricsRegistry] = None) -> None:
    """Fold the fleet observability plane (CMD_WINDOW rings) from a
    merged CMD_STATS payload into the registry.

    Exports ``bps_fleet_windows_held{server=}`` (window summaries
    parked per server — the elastic tests watch a drained server's
    ring re-appear on the survivor) and ``bps_fleet_publishes_total``
    (CMD_WINDOW frames accepted tier-wide).  Quiet when the fleet
    plane is unarmed (BYTEPS_TPU_FLEET unset): no gauge is registered
    and the snapshot is unchanged — the zero-overhead-when-off law
    every plane here follows."""
    reg = registry or get_registry()
    if not server_stats.get("fleet_armed"):
        return
    reg.gauge("bps_fleet_publishes_total",
              help="worker window summaries accepted by the server "
                   "tier (CMD_WINDOW), tier-wide").set(
                  int(server_stats.get("fleet_publishes", 0)))
    for sid, rec in (server_stats.get("servers") or {}).items():
        if not isinstance(rec, dict) or "fleet_windows_held" not in rec:
            continue
        reg.gauge("bps_fleet_windows_held",
                  help="worker window summaries parked in this "
                       "server's per-worker fleet rings",
                  labels={"server": str(sid)}).set(
                      int(rec.get("fleet_windows_held", 0)))


def update_embed(server_stats: dict,
                 registry: Optional[MetricsRegistry] = None) -> None:
    """Fold the row-sparse embedding plane from a merged CMD_STATS
    payload into the registry.

    Exports ``bps_embed_rows_served_total`` (rows the server tier has
    answered over the sparse pull/read planes) and
    ``bps_embed_table_bytes{server=}`` (declared embedding-table bytes
    resident per server — the recommendation-scale state that never fits
    a worker).  Quiet until some key actually declares an embedding
    (both numbers zero): no gauge is registered and the snapshot is
    unchanged — a dense job's metrics surface is untouched."""
    reg = registry or get_registry()
    served = int(server_stats.get("embed_rows_served", 0))
    if served or int(server_stats.get("embed_table_bytes", 0)):
        reg.gauge("bps_embed_rows_served_total",
                  help="embedding rows served by the PS tier over the "
                       "row-sparse pull/read planes").set(served)
    for sid, rec in (server_stats.get("servers") or {}).items():
        if not isinstance(rec, dict) or "embed_table_bytes" not in rec:
            continue
        if int(rec.get("embed_table_bytes", 0)) == 0 and not served:
            continue
        reg.gauge("bps_embed_table_bytes",
                  help="declared embedding-table bytes resident on this "
                       "server (rows x width x 4)",
                  labels={"server": str(sid)}).set(
                      int(rec.get("embed_table_bytes", 0)))


def update_round_lag(server_stats: dict, straggler_rounds: int,
                     registry: Optional[MetricsRegistry] = None
                     ) -> Dict[int, int]:
    """Fold a merged CMD_STATS payload into per-worker round-lag gauges.

    lag(w) = max over workers of round(w') - round(w): how many sync
    rounds worker w trails the most advanced worker by.  Logs a straggler
    warning for any worker trailing by more than `straggler_rounds`
    (``BYTEPS_TPU_STRAGGLER_ROUNDS``; 0 disables the warning).
    Returns {worker_id: lag}.

    In ASYNC mode the per-worker "round" degrades to a cumulative push
    count across all keys (there are no sync rounds), so the gauges still
    export — the spread is a real progress signal — but the warning is
    suppressed: nothing gates on a trailing worker there, and a
    many-key model would trip the threshold spuriously.
    """
    reg = registry or get_registry()
    workers = server_stats.get("workers") or {}
    is_async = bool(server_stats.get("async"))
    rounds = {int(w): int(s.get("round", 0)) for w, s in workers.items()}
    if not rounds:
        return {}
    lead = max(rounds.values())
    lags: Dict[int, int] = {}
    for w, r in rounds.items():
        lag = lead - r
        lags[w] = lag
        reg.gauge("bps_worker_round_lag",
                  help="sync rounds this worker trails the lead worker by",
                  labels={"worker": str(w)}).set(lag)
        if straggler_rounds > 0 and lag > straggler_rounds and not is_async:
            get_logger().warning(
                "straggler: worker %d trails the lead worker by %d rounds "
                "(> BYTEPS_TPU_STRAGGLER_ROUNDS=%d); its pushes gate every "
                "sync round's publish", w, lag, straggler_rounds)
    return lags


# ---------------------------------------------------------------------------
# Exporters: Prometheus HTTP endpoint + JSONL snapshot writer
# ---------------------------------------------------------------------------

# JSONL snapshot cadence; module-level so tests can shrink it.
JSONL_INTERVAL_S = 10.0


def json_safe(obj):
    """Strict-JSON sanitation: non-finite floats become strings (a bare
    ``Infinity`` would make the payload unparseable by the tools that
    exist to parse it).  The ONE copy of this walk — the JSON routes
    here and flightrec's postmortem bundles both ride it, so the two
    surfaces can never diverge on how the same value encodes."""
    if isinstance(obj, dict):
        return {str(k): json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [json_safe(v) for v in obj]
    if isinstance(obj, float) and (obj != obj or obj in
                                   (float("inf"), float("-inf"))):
        return str(obj)
    return obj


class TelemetryExporter:
    """Background export plane.

    - ``port > 0``: an HTTP thread serves ``GET /metrics`` (Prometheus
      text format; anything else 404s).  Each scrape first runs
      ``refresh`` (the api layer's CMD_STATS poll) so server-side gauges
      are scrape-fresh.
    - ``jsonl_path``: a writer thread appends one JSON snapshot line
      every ``JSONL_INTERVAL_S`` (and once at stop, so short runs still
      record something).  The file is size-capped: past ``max_log_mb``
      MiB (``BYTEPS_TPU_METRICS_LOG_MB``, default 64) it rotates to
      ``<path>.1`` (the previous ``.1`` becoming ``.2``, older dropped)
      — a week-long job's snapshot log stays bounded at ~3x the cap
      instead of growing without limit.
    """

    # Rotated generations kept beyond the live file (<path>.1, <path>.2).
    KEEP_GENERATIONS = 2

    def __init__(self, registry: MetricsRegistry, port: int = 0,
                 jsonl_path: str = "",
                 refresh: Optional[Callable[[], None]] = None,
                 max_log_mb: int = 64,
                 routes: Optional[Dict[str, Callable[[], object]]] = None):
        self.registry = registry
        self.jsonl_path = jsonl_path
        self.max_log_mb = max(1, int(max_log_mb))
        self.refresh = refresh
        # Extra JSON routes ({"/signals": fn, "/diagnosis": fn}): each
        # GET renders fn()'s return value as sanitized JSON — the signal
        # plane and doctor ride the SAME endpoint the Prometheus scrape
        # uses, so one open port serves all three.
        self.routes = dict(routes or {})
        self.port = 0
        self._want_port = int(port)
        self._httpd = None
        self._http_thread: Optional[threading.Thread] = None
        self._jsonl_stop = threading.Event()
        self._jsonl_thread: Optional[threading.Thread] = None

    def _do_refresh(self) -> None:
        if self.refresh is not None:
            try:
                self.refresh()
            except Exception:
                get_logger().debug("telemetry refresh failed", exc_info=True)

    def start(self) -> "TelemetryExporter":
        if self._want_port > 0:
            import http.server

            exporter = self

            class Handler(http.server.BaseHTTPRequestHandler):
                def do_GET(self):        # noqa: N802 (stdlib API)
                    path = self.path.split("?")[0]
                    route = exporter.routes.get(path)
                    if route is not None:
                        try:
                            body = json.dumps(
                                json_safe(route()), default=str).encode()
                        except Exception:
                            get_logger().exception(
                                "metrics route %s failed", path)
                            self.send_error(500)
                            return
                        self.send_response(200)
                        self.send_header("Content-Type",
                                         "application/json")
                        self.send_header("Content-Length",
                                         str(len(body)))
                        self.end_headers()
                        self.wfile.write(body)
                        return
                    if path not in ("/metrics", "/"):
                        self.send_error(404)
                        return
                    exporter._do_refresh()
                    body = exporter.registry.render_prometheus().encode()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type",
                        "text/plain; version=0.0.4; charset=utf-8")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)

                def log_message(self, *a):  # scrapes are not log events
                    pass

            self._httpd = http.server.ThreadingHTTPServer(
                ("", self._want_port), Handler)
            self._httpd.daemon_threads = True
            self.port = self._httpd.server_address[1]
            self._http_thread = threading.Thread(
                target=self._httpd.serve_forever, daemon=True,
                name="bps-metrics-http")
            self._http_thread.start()
            get_logger().info("metrics endpoint on :%d/metrics", self.port)
        if self.jsonl_path:
            self._jsonl_thread = threading.Thread(
                target=self._jsonl_loop, daemon=True,
                name="bps-metrics-jsonl")
            self._jsonl_thread.start()
        return self

    def _maybe_rotate(self) -> None:
        """Rotate the JSONL once it crosses the size cap.  Checked
        before each append so a single write can overshoot by at most
        one snapshot line — and a reader tailing the live path sees a
        truncate-to-fresh-file, the standard logrotate contract."""
        import os
        p = self.jsonl_path
        try:
            if os.path.getsize(p) < self.max_log_mb * (1 << 20):
                return
        except OSError:
            return          # no file yet (first write) — nothing to cap
        try:
            for gen in range(self.KEEP_GENERATIONS, 1, -1):
                src = f"{p}.{gen - 1}"
                if os.path.exists(src):
                    os.replace(src, f"{p}.{gen}")
            os.replace(p, f"{p}.1")
            get_logger().info(
                "metrics JSONL rotated at %d MiB: %s -> %s.1 "
                "(keeping %d generations)", self.max_log_mb, p, p,
                self.KEEP_GENERATIONS)
        except OSError:
            get_logger().warning("metrics JSONL rotation failed",
                                 exc_info=True)

    def write_snapshot(self) -> None:
        """Append one JSONL snapshot line now (also used by the loop)."""
        self._do_refresh()
        self._maybe_rotate()
        snap = self.registry.snapshot()
        for v in snap.values():
            if isinstance(v, dict) and "buckets" in v:
                # +Inf as a string: json.dumps would emit bare `Infinity`,
                # which is not valid JSON (strict parsers reject the line).
                v["buckets"] = [["+Inf" if le == float("inf") else le, c]
                                for le, c in v["buckets"]]
        line = json.dumps({"ts": time.time(), "metrics": snap},
                          default=str)
        with open(self.jsonl_path, "a") as f:
            f.write(line + "\n")

    def _jsonl_loop(self) -> None:
        while not self._jsonl_stop.wait(JSONL_INTERVAL_S):
            try:
                self.write_snapshot()
            except Exception:
                get_logger().exception("metrics JSONL snapshot failed")

    def stop(self) -> None:
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
        if self._jsonl_thread is not None:
            self._jsonl_stop.set()
            self._jsonl_thread.join(timeout=5)
            self._jsonl_thread = None
            try:
                self.write_snapshot()   # final line: short runs record too
            except Exception:
                get_logger().debug("final metrics snapshot failed",
                                   exc_info=True)
