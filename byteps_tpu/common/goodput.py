"""Goodput ledger: partition fleet wall-time EXACTLY into categories.

Per fleet-window (the aligned view ``doctor.fleet_windows_from_view``
produces), every worker's published wall-time is split into six
categories that sum EXACTLY to the total — no "other" bucket, no
unaccounted residue (asserted, like trace_analysis's critical-path
decomposition):

  compute          what remains after everything below is claimed —
                   time the worker was doing useful local work
  wire             push/pull serialization + socket time: the queue,
                   push_wire, encode and decode component seconds
  straggler_wait   server-side serve time — where waiting for the
                   slowest worker's round materializes under the
                   synchronous push_pull contract
  stall            barrier timeouts / stall watchdog events
  recovery         reconnects, replays, audit round losses
  disruption       deliberate control-plane churn: ring/membership
                   epochs, codec/knob switches, autoscale drains

``goodput_pct`` is compute's share of the total.  Category seconds come
from two sources of different fidelity: component seconds are MEASURED
(the KeySignal decomposition), event categories are ESTIMATED (each
event claims a fixed slice of the residual, scaled down when
oversubscribed) — the ledger is exact by construction either way, the
split between estimated categories is the approximate part.

Armed via the same plane as everything fleet (``BYTEPS_TPU_FLEET``);
exports ``bps_fleet_goodput_pct`` plus per-category gauges.
"""

from typing import Dict, List, Optional

from .telemetry import MetricsRegistry, get_registry

# The exact partition, in claim order.  compute is always LAST: it is
# the remainder, never claimed directly.
CATEGORIES = ("compute", "wire", "straggler_wait", "stall",
              "recovery", "disruption")

# Event-kind → category.  Matching is by exact kind, then by prefix
# before the first "_" (so future barrier_* kinds stay stalls without
# a table edit).
_EVENT_CATEGORY = {
    "barrier_timeout": "stall",
    "barrier_wait": "stall",
    "stall": "stall",
    "watchdog": "stall",
    "reconnected": "recovery",
    "conn_drop": "recovery",
    "conn_gave_up": "recovery",
    "replay": "recovery",
    "audit_lost_round": "recovery",
    "promote": "recovery",
    "ring_epoch": "disruption",
    "membership_epoch": "disruption",
    "knob_switch": "disruption",
    "codec_switch": "disruption",
    "evicted": "disruption",
    "autoscale": "disruption",
    "drain": "disruption",
}
_PREFIX_CATEGORY = {"barrier": "stall", "conn": "recovery",
                    "audit": "recovery"}

# Each event claims this many seconds of the window's residual time.
# A deliberate coarse estimate — when events oversubscribe the residual
# their claims scale down proportionally, so the partition stays exact.
EVENT_CLAIM_S = 1.0

# Σ|categories| == total must hold to this RELATIVE tolerance; beyond
# it the ledger raises — an inexact partition is a bug, not a rounding
# footnote.
_REL_TOL = 1e-6


def event_category(kind: str) -> Optional[str]:
    """Category an event kind bills to, or None (uncategorized events
    cost nothing — they are informational, e.g. init/shutdown)."""
    cat = _EVENT_CATEGORY.get(kind)
    if cat:
        return cat
    return _PREFIX_CATEGORY.get(kind.split("_", 1)[0])


def worker_ledger(doc: dict) -> Dict[str, float]:
    """Partition ONE worker's published window (a fleet publish doc)
    into category seconds summing exactly to its wall time (dur_s).

    Measured component seconds claim first (scaled down proportionally
    if they exceed wall — components can overlap in time); event
    claims split what remains; compute is the exact remainder.  With
    the devprof plane armed the doc carries a measured
    ``device_compute`` component (block_until_ready device seconds):
    it claims alongside wire/wait and lands IN the compute bucket, so
    ``compute`` becomes measured-device-seconds + unexplained remainder
    instead of pure inference.  Docs without it (devprof off, pre-PR-20
    workers) partition exactly as before — device_compute=0 is
    arithmetically the old ledger."""
    wall = max(0.0, float(doc.get("dur_s") or 0.0))
    comps = doc.get("components") or {}
    wire = sum(float(comps.get(c) or 0.0)
               for c in ("queue", "push_wire", "encode", "decode"))
    wait = float(comps.get("serve") or 0.0)
    dev = max(0.0, float(comps.get("device_compute") or 0.0))
    wire, wait = max(0.0, wire), max(0.0, wait)
    measured = wire + wait + dev
    if measured > wall and measured > 0.0:
        scale = wall / measured
        wire *= scale
        wait *= scale
        dev *= scale
    residual = wall - wire - wait - dev
    claims = {"stall": 0.0, "recovery": 0.0, "disruption": 0.0}
    for kind, n in (doc.get("events") or {}).items():
        cat = event_category(str(kind))
        if cat in claims:
            claims[cat] += max(0, int(n)) * EVENT_CLAIM_S
    claimed = sum(claims.values())
    if claimed > residual and claimed > 0.0:
        scale = residual / claimed
        claims = {c: v * scale for c, v in claims.items()}
        claimed = residual
    ledger = {"compute": dev + (residual - claimed), "wire": wire,
              "straggler_wait": wait, **claims}
    total = sum(ledger.values())
    if abs(total - wall) > _REL_TOL * max(1.0, wall):
        raise AssertionError(
            f"goodput ledger is not an exact partition: "
            f"sum={total!r} wall={wall!r} doc window="
            f"{doc.get('window')!r} worker={doc.get('worker')!r}")
    return ledger


def fleet_ledger(fleet_window: dict) -> dict:
    """Sum every worker's ledger for one aligned fleet window.

    Returns {"window", "n_workers", "total_s", "seconds": {cat: s},
    "pct": {cat: share}, "goodput_pct"}; the exact-partition law holds
    for the sum too (asserted)."""
    seconds = {c: 0.0 for c in CATEGORIES}
    workers = fleet_window.get("workers") or {}
    for doc in workers.values():
        for c, v in worker_ledger(doc).items():
            seconds[c] += v
    total = sum(seconds.values())
    wall = sum(max(0.0, float(d.get("dur_s") or 0.0))
               for d in workers.values())
    if abs(total - wall) > _REL_TOL * max(1.0, wall):
        raise AssertionError(
            f"fleet ledger is not an exact partition: "
            f"sum={total!r} wall={wall!r} window="
            f"{fleet_window.get('window')!r}")
    pct = {c: (100.0 * v / total if total > 0.0 else 0.0)
           for c, v in seconds.items()}
    return {"window": fleet_window.get("window"),
            "n_workers": len(workers),
            "total_s": total,
            "seconds": seconds,
            "pct": pct,
            "goodput_pct": pct["compute"]}


def update_goodput(ledger: dict,
                   registry: Optional[MetricsRegistry] = None) -> None:
    """Export one fleet ledger to the registry:
    ``bps_fleet_goodput_pct`` plus
    ``bps_fleet_time_pct{category=}`` per category.  Callers only
    invoke this when the fleet plane is armed, so there is no gauge
    when BYTEPS_TPU_FLEET is off (the quiet-when-unarmed law)."""
    reg = registry or get_registry()
    reg.gauge("bps_fleet_goodput_pct",
              help="share of fleet wall-time spent computing "
                   "(goodput ledger, per fleet window)").set(
                  float(ledger.get("goodput_pct") or 0.0))
    for cat in CATEGORIES:
        reg.gauge("bps_fleet_time_pct",
                  help="fleet wall-time share per goodput category "
                       "(categories sum exactly to 100)",
                  labels={"category": cat}).set(
                      float((ledger.get("pct") or {}).get(cat, 0.0)))
