"""Continuous diagnosis engine (`bps doctor`): declarative rules over
the windowed signal plane.

``common/signals.py`` closes one window summary every
``BYTEPS_TPU_SIGNAL_WINDOW_S`` seconds; this module evaluates a fixed
set of **rules** against the window history so the system names its own
bottlenecks and failures instead of waiting for a human to correlate
bps_top, trace_analyze and postmortem.py by eye.  Every firing produces
a structured **Finding**::

    {"rule", "severity", "subject", "summary", "evidence",
     "playbook", "window", "first_window", "ts"}

fed four ways: the log (WARNING/ERROR on open, once), the flight
recorder (``doctor_finding`` events, so findings land on postmortem
timelines), the ``bps_doctor_findings_total{rule=}`` counter, and
``bps.get_diagnosis()``.  ``playbook`` is a stable anchor into
``docs/troubleshooting.md`` (``#rule-<id>``) — drift between rule ids
and playbook anchors is pinned by ``tools/check_doctor_docs.py`` as a
tier-1 test.

The SAME rules run offline: ``tools/bps_doctor.py`` replays them over a
postmortem bundle's recorded window history or a metrics JSONL from a
dead run — rules therefore consume only what both paths carry (the
scalar metrics series, event counts, and the optional
transport/server sections), via the :class:`RuleCtx` helpers.
"""

from __future__ import annotations

import dataclasses
import re
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

from .logging import get_logger

PLAYBOOK = "docs/troubleshooting.md"

SEV_WARN = "warn"
SEV_ERROR = "error"
SEV_CRITICAL = "critical"
_SEV_ORDER = {SEV_WARN: 0, SEV_ERROR: 1, SEV_CRITICAL: 2}

# Default thresholds, merged with per-engine overrides.  Every number a
# rule compares against lives here so tests can pin boundaries and
# operators can retune without touching rule code.
DEFAULT_THRESHOLDS = {
    # persistent_straggler: same worker is the max-lag worker with lag
    # >= straggler_lag for >= straggler_windows consecutive windows.
    "straggler_lag": 1,
    "straggler_windows": 2,
    # round_lag_growth: a worker's lag strictly grew across this many
    # consecutive windows (it is not just behind — it is falling).
    "lag_growth_windows": 3,
    # lane_credit_imbalance: with >= 2 lanes to a server, the busiest
    # lane carries > imbalance_ratio x its sibling lanes COMBINED, above
    # a traffic floor (idle lanes on a quiet link are not a finding).
    "lane_imbalance_ratio": 4.0,
    "lane_min_bytes": 16 * 1024 * 1024,
    # recv_pool_miss_rate: in-window miss fraction above this, with at
    # least pool_min_events checkouts in the window.
    "pool_miss_rate": 0.5,
    "pool_min_events": 32,
    # fusion_dilution: deadline flushes dominate bucket flushes — the
    # fusion layer is shipping mostly-empty buckets (threshold too big
    # for the model, or the producer trickles leaves).
    "fusion_min_flushes": 4,
    "fusion_deadline_ratio": 2.0,
    # server_hot_shard: one server's load share (keys_owned weighted by
    # bytes when per-server bytes are known) above hot_shard_ratio x the
    # fair share, with >= 2 servers and >= hot_shard_min_keys total.
    "hot_shard_ratio": 2.0,
    "hot_shard_min_keys": 8,
    # tuner_thrash: a key switched codecs in MORE THAN thrash_switches
    # of the last thrash_windows windows — the adaptive-compression
    # loop is oscillating instead of converging (hysteresis too short
    # for the workload's class noise, or a key genuinely on a
    # wire/compute boundary).
    "tuner_thrash_windows": 6,
    "tuner_thrash_switches": 2,
    # knob_thrash: the GLOBAL knob table (CMD_KNOB: fusion_bytes /
    # compress_threads / wire_conns) switched in MORE THAN
    # knob_thrash_switches of the last knob_thrash_windows windows —
    # every switch re-plans fusion layouts / resizes pools / redials
    # lanes fleet-wide, so an oscillating knob loop is far costlier
    # than a thrashing per-key codec (raise the tuner's knob cooldown,
    # or pin the knobs with BYTEPS_TPU_KNOB_ACTUATE=0).
    "knob_thrash_windows": 6,
    "knob_thrash_switches": 2,
    # param_version_stall: an opt-armed key's completed_round grew while
    # its param_version did not, for this many consecutive windows — the
    # server-resident update stage is wedged or misconfigured (params
    # never seeded, a gradient/params length mismatch, or a mode switch
    # that silently reverted to sums).
    "param_stall_windows": 2,
    # embedding_cache_thrash: the hot-row cache's in-window hit rate sat
    # below embed_cache_hit_floor for embed_thrash_windows consecutive
    # windows WHILE sparse pull bytes kept growing — every lookup is
    # paying wire (working set larger than BYTEPS_TPU_SPARSE_CACHE_ROWS,
    # or publish cadence churns param_version so fast every version
    # invalidates the cache before it is re-read).  A window needs at
    # least embed_min_lookup_rows cache decisions to count (a cold or
    # idle reader is not thrashing).
    "embed_thrash_windows": 2,
    "embed_cache_hit_floor": 0.25,
    "embed_min_lookup_rows": 64,
    # replication_lag: a chain-replication owner's publish cursor ran
    # more than repl_lag_rounds ahead of its successor's ack for
    # repl_lag_windows consecutive windows — the successor (or the peer
    # link) cannot keep up, so the zero-loss failover window is growing
    # (docs/elasticity.md "zero-loss law"): a kill now loses up to that
    # many rounds of pull availability, and with BYTEPS_TPU_REPL_LAG=0
    # every pull is parked behind the backlog.
    "repl_lag_rounds": 3,
    "repl_lag_windows": 2,
    # mfu_regression: the windowed MFU dropped more than
    # mfu_regress_frac vs the previous window's WHILE wire seconds
    # stayed flat (grew less than mfu_wire_flat_frac) — the slowdown is
    # on the DEVICE side (thermal throttle, a preempted chip, a new
    # compilation gone wrong), not a wire story the other rules would
    # catch.  Needs the devprof plane armed (BYTEPS_TPU_DEVPROF=1);
    # quiet when either window has no MFU sample.
    "mfu_regress_frac": 0.25,
    "mfu_wire_flat_frac": 0.25,
    # ---- fleet rules (evaluated over the MERGED per-worker view the
    # CMD_FLEET plane serves, docs/monitoring.md "Fleet plane"; the
    # windows these rules see are ALIGNED fleet windows — one entry per
    # window index with every worker's published row) ----
    # fleet_straggler_confirmed: the SAME worker is max-round-lag blame
    # in >= fleet_quorum_frac of the workers' views (at least
    # fleet_straggler_min_lag rounds behind) for
    # fleet_straggler_windows consecutive fleet windows.  One worker's
    # local persistent_straggler names whoever IT waited on; this is
    # the fleet-confirmed version — everyone agrees who is slow.
    "fleet_quorum_frac": 0.5,
    "fleet_straggler_windows": 2,
    "fleet_straggler_min_lag": 1,
    # clock_skew: a worker's NTP-style offset estimate vs its rank-0
    # server drifts more than clock_skew_ms from the fleet MEDIAN
    # estimate for clock_skew_windows consecutive fleet windows — its
    # timestamps (trace spans, window anchors) can no longer be merged
    # onto the fleet timeline without correction.
    "clock_skew_ms": 50.0,
    "clock_skew_windows": 2,
    # codec_epoch_divergence: two workers report the SAME codec epoch
    # for a key but DIFFERENT active codec names, with no switch
    # pending on either side, for codec_divergence_windows consecutive
    # fleet windows.  The epoch->codec mapping is server-authoritative,
    # so past the declared boundary this must never happen — it means
    # some worker merged a renegotiation wrong and the wire formats
    # have forked.
    "codec_divergence_windows": 2,
    # signal_disagreement: a key's per-worker wire_mbps spread exceeds
    # signal_spread_ratio (max/min) across workers while the fastest
    # view moves at least signal_min_mbps — the tuner-is-flying-blind
    # signal: worker 0 negotiates codecs from a bandwidth sample the
    # other N-1 do not see.
    "signal_spread_ratio": 4.0,
    "signal_min_mbps": 1.0,
}

_SERIES_RE = re.compile(r'^([A-Za-z_:][A-Za-z0-9_:]*)\{(.*)\}$')
_LABEL_RE = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def playbook_anchor(rule_id: str) -> str:
    return f"{PLAYBOOK}#rule-{rule_id}"


def parse_series(metrics: dict, name: str) -> Dict[tuple, float]:
    """Labeled series from a flat registry-snapshot dict: keys look like
    ``bps_worker_round_lag{worker="1"}``.  Returns {((label, value),
    ...): number}; the unlabeled series (bare ``name``) keys as ()."""
    out: Dict[tuple, float] = {}
    for k, v in metrics.items():
        if not isinstance(v, (int, float)):
            continue
        if k == name:
            out[()] = float(v)
            continue
        m = _SERIES_RE.match(k)
        if m and m.group(1) == name:
            labels = tuple(sorted(
                (lk, lv.replace('\\"', '"').replace("\\\\", "\\"))
                for lk, lv in _LABEL_RE.findall(m.group(2))))
            out[labels] = float(v)
    return out


class RuleCtx:
    """What a rule sees: the window history (oldest..newest summaries)
    plus delta/series helpers.  Counters are cumulative in the metrics
    snapshot, so in-window activity is the DELTA between consecutive
    windows' snapshots; gauges are read from the newest snapshot as-is
    — the "counter deltas vs gauge snapshots" law the aggregation tests
    pin."""

    def __init__(self, windows: List[dict],
                 thresholds: Optional[dict] = None):
        self.windows = list(windows)
        self.cur = self.windows[-1] if self.windows else {}
        self.prev = self.windows[-2] if len(self.windows) > 1 else {}
        self.th = dict(DEFAULT_THRESHOLDS)
        if thresholds:
            self.th.update(thresholds)

    # -- metrics helpers ----------------------------------------------------
    def metric(self, name: str, default: float = 0.0) -> float:
        v = (self.cur.get("metrics") or {}).get(name, default)
        return float(v) if isinstance(v, (int, float)) else default

    def series(self, name: str, window: Optional[dict] = None
               ) -> Dict[tuple, float]:
        w = self.cur if window is None else window
        return parse_series(w.get("metrics") or {}, name)

    def delta(self, name: str) -> float:
        """Counter delta across the last window (clamped at 0: a process
        restart between snapshots resets counters, which must read as
        "no activity", not a huge negative).  With only one window there
        is no baseline — the cumulative total could be hours old, so the
        delta is 0, never the total (counter rules need two windows;
        gauge rules fire from the first)."""
        if not self.prev:
            return 0.0
        cur = (self.cur.get("metrics") or {}).get(name, 0.0)
        prev = (self.prev.get("metrics") or {}).get(name, 0.0)
        if not isinstance(cur, (int, float)) or \
                not isinstance(prev, (int, float)):
            return 0.0
        return max(0.0, float(cur) - float(prev))

    def events(self, kind: str) -> int:
        return int((self.cur.get("events") or {}).get(kind, 0))

    def lag_map(self, window: dict) -> Dict[str, int]:
        """{worker_id: round lag} from one window's gauges."""
        out: Dict[str, int] = {}
        for labels, v in self.series("bps_worker_round_lag",
                                     window).items():
            d = dict(labels)
            if "worker" in d:
                out[d["worker"]] = int(v)
        return out


@dataclasses.dataclass
class Rule:
    id: str
    severity: str
    summary: str              # one-line description (docs/rule table)
    fn: Callable[[RuleCtx], List[dict]]   # -> [{"subject", "message",
    #                                           "evidence"}, ...]


# ---------------------------------------------------------------------------
# Rule implementations.  Each returns a list of firings (empty = quiet);
# a firing's "subject" keys the finding's open/close identity across
# windows (e.g. the straggling worker id), so a persisting condition is
# ONE finding that stays open, not a new one per window.
# ---------------------------------------------------------------------------
def _r_persistent_straggler(ctx: RuleCtx) -> List[dict]:
    need = int(ctx.th["straggler_windows"])
    min_lag = int(ctx.th["straggler_lag"])
    if len(ctx.windows) < need:
        return []
    worst: Optional[str] = None
    lags: List[int] = []
    for w in ctx.windows[-need:]:
        lag = ctx.lag_map(w)
        if not lag:
            return []
        wid, l = max(lag.items(), key=lambda kv: kv[1])
        if l < min_lag:
            return []
        if worst is None:
            worst = wid
        elif wid != worst:
            return []
        lags.append(l)
    return [{"subject": f"worker={worst}",
             "message": (f"worker {worst} has trailed the lead worker by "
                         f">= {min_lag} round(s) for {need} consecutive "
                         f"windows (lag history {lags}); its pushes gate "
                         f"every sync round's publish"),
             "evidence": {"worker": worst, "lags": lags,
                          "windows": need}}]


def _r_round_lag_growth(ctx: RuleCtx) -> List[dict]:
    need = int(ctx.th["lag_growth_windows"])
    if len(ctx.windows) < need:
        return []
    hist = [ctx.lag_map(w) for w in ctx.windows[-need:]]
    out = []
    for wid in hist[-1]:
        series = [h.get(wid) for h in hist]
        if any(v is None for v in series):
            continue
        if all(series[i] < series[i + 1] for i in range(len(series) - 1)):
            out.append({
                "subject": f"worker={wid}",
                "message": (f"worker {wid}'s round lag grew every window "
                            f"for {need} windows ({series}): it is not "
                            f"just behind, it is falling further behind "
                            f"every round"),
                "evidence": {"worker": wid, "lags": series}})
    return out


def _r_lane_credit_imbalance(ctx: RuleCtx) -> List[dict]:
    # Lane rows carry LIFETIME byte counters — the skew that matters is
    # this window's delta (lifetime totals both dilute a fresh wedge
    # behind hours of balanced history and pin an old, resolved skew
    # open forever).  No previous transport section = no baseline = no
    # verdict, the same law ctx.delta() applies to counters.
    cur_rows = (ctx.cur.get("transport") or {}).get("lanes")
    prev_rows = (ctx.prev.get("transport") or {}).get("lanes")
    if not cur_rows or prev_rows is None:
        return []
    prev_bytes = {(r.get("server"), r.get("lane")):
                  int(r.get("bytes_total", 0)) for r in prev_rows}
    by_srv: Dict[object, list] = {}
    for row in cur_rows:
        key = (row.get("server"), row.get("lane"))
        d = max(0, int(row.get("bytes_total", 0))
                - prev_bytes.get(key, 0))
        by_srv.setdefault(row.get("server"), []).append(d)
    out = []
    ratio = float(ctx.th["lane_imbalance_ratio"])
    floor = int(ctx.th["lane_min_bytes"])
    for srv, deltas in by_srv.items():
        if len(deltas) < 2:
            continue
        total = sum(deltas)
        if total < floor:
            continue
        worst = max(deltas)
        rest = total - worst
        # vs the REST COMBINED, not the mean: with k lanes the max can
        # never exceed k x the mean, so a mean-ratio test can't fire on
        # 2 lanes no matter how skewed they are.
        if worst > ratio * max(1, rest):
            out.append({
                "subject": f"server={srv}",
                "message": (f"server {srv}'s busiest data lane carried "
                            f"{worst} of {total} bytes this window "
                            f"(> {ratio:g}x its {len(deltas) - 1} "
                            f"sibling lane(s) combined): the "
                            f"byte-credit scheduler is pinned to one "
                            f"lane — look for one giant partition or a "
                            f"wedged lane"),
                "evidence": {"server": srv, "lane_bytes": deltas,
                             "total": total}})
    return out


def _r_recv_pool_miss_rate(ctx: RuleCtx) -> List[dict]:
    hits = ctx.delta("bps_transport_pool_hits")
    misses = ctx.delta("bps_transport_pool_misses")
    events = hits + misses
    if events < int(ctx.th["pool_min_events"]):
        return []
    rate = misses / events
    if rate <= float(ctx.th["pool_miss_rate"]):
        return []
    return [{"subject": "recv_pool",
             "message": (f"receive-buffer pool missed on "
                         f"{rate:.0%} of {events:.0f} checkouts this "
                         f"window: payloads exceed the pool's size "
                         f"classes or churn outruns its depth — every "
                         f"miss is a fresh allocation on the receiver "
                         f"thread"),
             "evidence": {"hits": hits, "misses": misses,
                          "miss_rate": round(rate, 4)}}]


def _r_fusion_dilution(ctx: RuleCtx) -> List[dict]:
    deadline = ctx.delta("bps_fusion_deadline_flushes")
    full = ctx.delta("bps_fusion_full_flushes")
    if deadline + full < int(ctx.th["fusion_min_flushes"]):
        return []
    if deadline <= float(ctx.th["fusion_deadline_ratio"]) * max(1.0, full):
        return []
    return [{"subject": "fusion",
             "message": (f"{deadline:.0f} fusion buckets flushed on the "
                         f"FLUSH_MS deadline vs {full:.0f} flushed full "
                         f"this window: buckets ship mostly empty — "
                         f"lower BYTEPS_TPU_FUSION_BYTES or raise "
                         f"FLUSH_MS to match the producer's pace"),
             "evidence": {"deadline_flushes": deadline,
                          "full_flushes": full}}]


def _r_server_hot_shard(ctx: RuleCtx) -> List[dict]:
    owned = {dict(k).get("server"): v
             for k, v in ctx.series("bps_keys_owned").items()}
    owned = {s: int(v) for s, v in owned.items() if s is not None}
    if len(owned) < 2:
        return []
    total = sum(owned.values())
    if total < int(ctx.th["hot_shard_min_keys"]):
        return []
    # Weight by per-server bytes when the server sections carry a row
    # for EVERY owned server in this window AND the previous one (the
    # weight is the in-window bytes_in delta — bytes_in is a lifetime
    # counter, and a partial section, e.g. one momentarily-unreachable
    # server's row missing, would otherwise zero that server's load and
    # crown whoever has a row the "hot" one).  keys_owned alone
    # otherwise.
    def _bytes_rows(window: dict) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for sid, row in ((window.get("server") or {}).get("servers")
                         or {}).items():
            if isinstance(row, dict) and isinstance(
                    row.get("bytes_in"), (int, float)):
                out[str(sid)] = float(row["bytes_in"])
        return out

    cur_b, prev_b = _bytes_rows(ctx.cur), _bytes_rows(ctx.prev)
    have_all = all(s in cur_b and s in prev_b for s in owned)
    delta_b = ({s: max(0.0, cur_b[s] - prev_b[s]) for s in owned}
               if have_all else {})
    if have_all and sum(delta_b.values()) > 0:
        load = {s: owned.get(s, 0) * delta_b[s] for s in owned}
        basis = "keys_owned x bytes_in"
    else:
        load = {s: float(v) for s, v in owned.items()}
        basis = "keys_owned"
    tot = sum(load.values())
    if tot <= 0:
        return []
    fair = tot / len(load)
    hot, hot_load = max(load.items(), key=lambda kv: kv[1])
    if hot_load <= float(ctx.th["hot_shard_ratio"]) * fair:
        return []
    return [{"subject": f"server={hot}",
             "message": (f"server {hot} carries {hot_load / tot:.0%} of "
                         f"the {basis} load across {len(load)} servers "
                         f"(fair share {1 / len(load):.0%}): a hot "
                         f"shard — rebalance the ring (vnodes) or drain "
                         f"keys off it"),
             "evidence": {"server": hot, "basis": basis,
                          "load": {s: round(v, 1)
                                   for s, v in load.items()},
                          "keys_owned": owned}}]


def _r_replication_lag(ctx: RuleCtx) -> List[dict]:
    """Chain replication (CMD_REPL) can't keep up: a server's newest
    published round trails its ring successor's ack by more than
    ``repl_lag_rounds`` for ``repl_lag_windows`` consecutive windows.
    Reads the per-server rows (lag is a property of one owner→successor
    edge, not of the tier) straight from the window's server section —
    the same rows the autoscaler consumes."""
    need = int(ctx.th["repl_lag_windows"])
    floor = int(ctx.th["repl_lag_rounds"])
    if len(ctx.windows) < need:
        return []

    def _lag_rows(window: dict) -> Dict[str, int]:
        sec = window.get("server") or {}
        if not sec.get("repl_armed"):
            return {}
        out: Dict[str, int] = {}
        for sid, row in (sec.get("servers") or {}).items():
            if isinstance(row, dict) and isinstance(
                    row.get("repl_lag_rounds"), (int, float)):
                out[str(sid)] = int(row["repl_lag_rounds"])
        return out

    recent = [_lag_rows(w) for w in ctx.windows[-need:]]
    if not all(recent):
        return []      # replication unarmed or rows missing in a window
    out: List[dict] = []
    for sid, lag in recent[-1].items():
        history = [r.get(sid, 0) for r in recent]
        if all(v > floor for v in history):
            out.append({
                "subject": f"server={sid}",
                "message": (
                    f"server {sid}'s replication to its ring successor "
                    f"trails its publishes by {lag} rounds (> "
                    f"{floor}) for {need} consecutive windows: the "
                    f"zero-loss failover window is growing — check the "
                    f"successor's load / the peer link, or raise "
                    f"BYTEPS_TPU_REPL_LAG only if pulls are parking"),
                "evidence": {"server": sid, "lag_history": history,
                             "floor": floor, "windows": need}})
    return out


def _r_nonfinite_gradients(ctx: RuleCtx) -> List[dict]:
    d = ctx.delta("bps_grad_nonfinite_total")
    if d <= 0:
        return []
    bad_keys = sorted(
        dict(labels).get("key", "?")
        for labels, v in ctx.series("bps_grad_nonfinite").items()
        if v > 0)
    return [{"subject": "nonfinite",
             "message": (f"{d:.0f} non-finite gradient sample(s) this "
                         f"window (keys: {', '.join(bad_keys) or '?'}): "
                         f"NaN/Inf is in the training values — see the "
                         f"GRADIENT HEALTH errors for key/round/worker "
                         f"attribution"),
             "evidence": {"new_samples": d, "keys": bad_keys}}]


def _r_audit_mismatch(ctx: RuleCtx) -> List[dict]:
    mism = ctx.delta("bps_audit_mismatch_total")
    skew = ctx.delta("bps_audit_round_skew_total")
    if mism <= 0 and skew <= 0:
        return []
    what = []
    if mism:
        what.append(f"{mism:.0f} digest mismatch(es)")
    if skew:
        what.append(f"{skew:.0f} lost/skewed round(s)")
    return [{"subject": "audit",
             "message": (f"consistency auditor flagged "
                         f"{' and '.join(what)} this window: pulled "
                         f"bytes differ from what the server published "
                         f"— see the AUDIT errors and "
                         f"bps.get_audit(cross_check=True)"),
             "evidence": {"mismatches": mism, "round_skew": skew}}]


def _r_tuner_thrash(ctx: RuleCtx) -> List[dict]:
    m = int(ctx.th["tuner_thrash_windows"])
    n = int(ctx.th["tuner_thrash_switches"])
    if len(ctx.windows) < 2:
        return []
    wins = ctx.windows[-(m + 1):]
    # A "switch window" for a key = its bps_tuner_key_switches_total
    # series grew across that window (counter delta law: consecutive
    # snapshot pairs, restart-clamped).
    switch_windows: Dict[str, int] = {}
    for prev, cur in zip(wins, wins[1:]):
        pm = parse_series(prev.get("metrics") or {},
                          "bps_tuner_key_switches_total")
        cm = parse_series(cur.get("metrics") or {},
                          "bps_tuner_key_switches_total")
        prev_by_key = {dict(lbl).get("key"): v for lbl, v in pm.items()}
        for lbl, v in cm.items():
            key = dict(lbl).get("key")
            if key is None:
                continue
            if v - float(prev_by_key.get(key, 0.0)) > 0:
                switch_windows[key] = switch_windows.get(key, 0) + 1
    out = []
    for key, cnt in sorted(switch_windows.items()):
        if cnt <= n:
            continue
        classes = [
            ((w.get("keys") or {}).get(key) or {}).get("class", "-")
            for w in wins[1:]]
        out.append({
            "subject": f"key={key}",
            "message": (f"key {key} switched codecs in {cnt} of the "
                        f"last {len(wins) - 1} windows (class history "
                        f"{classes}): the adaptive-compression tuner is "
                        f"thrashing instead of converging — raise "
                        f"BYTEPS_TPU_TUNER_HOLD / _BLACKLIST, or pin "
                        f"this key's codec by hand"),
            "evidence": {"key": key, "switch_windows": cnt,
                         "windows": len(wins) - 1,
                         "class_history": classes}})
    return out


def _r_knob_thrash(ctx: RuleCtx) -> List[dict]:
    m = int(ctx.th["knob_thrash_windows"])
    n = int(ctx.th["knob_thrash_switches"])
    if len(ctx.windows) < 2:
        return []
    wins = ctx.windows[-(m + 1):]
    # A "switch window" = bps_knob_switches_total grew across it (the
    # counter delta law; the counter increments once per applied global
    # knob-table epoch on this worker).
    switch_windows = 0
    history = []
    for prev, cur in zip(wins, wins[1:]):
        pv = parse_series(prev.get("metrics") or {},
                          "bps_knob_switches_total").get((), 0.0)
        cv = parse_series(cur.get("metrics") or {},
                          "bps_knob_switches_total").get((), 0.0)
        switched = cv - pv > 0
        if switched:
            switch_windows += 1
        entry = {"window": int(cur.get("window", -1)),
                 "switched": switched,
                 "epoch": int(parse_series(
                     cur.get("metrics") or {},
                     "bps_knob_epoch").get((), 0.0))}
        values = {}
        for lbl, v in parse_series(cur.get("metrics") or {},
                                   "bps_knob_value").items():
            knob = dict(lbl).get("knob")
            if knob:
                values[knob] = int(v)
        if values:
            entry["knobs"] = values
        history.append(entry)
    if switch_windows <= n:
        return []
    return [{
        "subject": "knob_table",
        "message": (f"the global knob table switched in "
                    f"{switch_windows} of the last {len(wins) - 1} "
                    f"windows: every CMD_KNOB epoch re-plans fusion "
                    f"layouts / resizes pools / redials lanes "
                    f"fleet-wide — the knob loop is oscillating "
                    f"instead of converging; raise the tuner's knob "
                    f"cooldown or pin the knobs with "
                    f"BYTEPS_TPU_KNOB_ACTUATE=0"),
        "evidence": {"switch_windows": switch_windows,
                     "windows": len(wins) - 1,
                     "knob_history": history}}]


def _r_param_version_stall(ctx: RuleCtx) -> List[dict]:
    """Server-resident optimizer wedge: a key whose rounds keep
    completing (completed_round grows) while its param_version does not
    — the update stage stopped publishing parameters (unseeded params,
    a gradient/params length mismatch, or a silent revert to sums).
    Reads the CMD_STATS server section both modes carry, so the offline
    bundle replay fires identically (and stays quiet when the section
    is absent)."""
    need = int(ctx.th["param_stall_windows"])
    if len(ctx.windows) < need + 1:
        return []
    wins = ctx.windows[-(need + 1):]

    def _opt_rows(window: dict) -> Dict[str, dict]:
        # Live windows carry the minimal `opt_keys` slice (signals.py
        # strips the full per-key map); raw CMD_STATS payloads (offline
        # replays, tests) carry `keys` — read both.
        sec = window.get("server") or {}
        out: Dict[str, dict] = {}
        for src in (sec.get("opt_keys"), sec.get("keys")):
            for k, row in (src or {}).items():
                if isinstance(row, dict) and int(row.get("opt_mode", 0)):
                    out.setdefault(str(k), row)
        return out

    newest = _opt_rows(wins[-1])
    if not newest:
        return []
    out = []
    for k, row in sorted(newest.items()):
        stalled = 0
        for prev, cur in zip(wins, wins[1:]):
            pr = _opt_rows(prev).get(k)
            cr = _opt_rows(cur).get(k)
            if pr is None or cr is None:
                break
            dr = int(cr.get("completed_round", 0)) \
                - int(pr.get("completed_round", 0))
            dv = int(cr.get("param_version", 0)) \
                - int(pr.get("param_version", 0))
            if dr > 0 and dv <= 0:
                stalled += 1
            else:
                break
        if stalled < need:
            continue
        out.append({
            "subject": f"key={k}",
            "message": (f"key {k} completed "
                        f"{int(row.get('completed_round', 0))} rounds "
                        f"but param_version sits at "
                        f"{int(row.get('param_version', 0))} for "
                        f"{stalled} consecutive windows: the "
                        f"server-resident update stage is wedged or "
                        f"mode-mismatched — check the server log for "
                        f"unseeded-params / length-mismatch warnings "
                        f"and the CMD_OPT doc (fetch_opt_docs)"),
            "evidence": {"key": k,
                         "completed_round":
                             int(row.get("completed_round", 0)),
                         "param_version":
                             int(row.get("param_version", 0)),
                         "opt_mode": int(row.get("opt_mode", 0)),
                         "stalled_windows": stalled}})
    return out


def _r_embedding_cache_thrash(ctx: RuleCtx) -> List[dict]:
    """Row-sparse lookup tier (docs/sparse-embedding.md): the hot-row
    cache stopped absorbing the zipf head — the hit rate collapsed for
    consecutive windows while sparse pull bytes kept growing, so every
    lookup pays a wire round trip the cache exists to eliminate.
    Counter-delta rule: needs windows+1 snapshots, quiet on idle/cold
    readers (per-window lookup floor) and when wire traffic is not
    actually flowing (a low rate with no pull bytes is a version-pinned
    cache serving nothing — not thrash)."""
    need = int(ctx.th["embed_thrash_windows"])
    floor = float(ctx.th["embed_cache_hit_floor"])
    min_rows = int(ctx.th["embed_min_lookup_rows"])
    if len(ctx.windows) < need + 1:
        return []
    wins = ctx.windows[-(need + 1):]

    def _m(window: dict, name: str) -> float:
        v = (window.get("metrics") or {}).get(name, 0.0)
        return float(v) if isinstance(v, (int, float)) else 0.0

    rates: List[float] = []
    pull_bytes: List[int] = []
    for prev, cur in zip(wins, wins[1:]):
        dh = max(0.0, _m(cur, "bps_embed_cache_hits")
                 - _m(prev, "bps_embed_cache_hits"))
        dm = max(0.0, _m(cur, "bps_embed_cache_misses")
                 - _m(prev, "bps_embed_cache_misses"))
        db = max(0.0, _m(cur, "bps_embed_pull_bytes_total")
                 - _m(prev, "bps_embed_pull_bytes_total"))
        if dh + dm < min_rows or db <= 0.0:
            return []
        rate = dh / (dh + dm)
        if rate >= floor:
            return []
        rates.append(round(rate, 4))
        pull_bytes.append(int(db))
    return [{
        "subject": "embed-cache",
        "message": (f"embedding hot-row cache hit rate sat below "
                    f"{floor:.0%} for {need} consecutive windows "
                    f"(history {rates}) while sparse pull bytes kept "
                    f"growing ({pull_bytes}): every lookup is paying "
                    f"wire — the working set outgrew "
                    f"BYTEPS_TPU_SPARSE_CACHE_ROWS, or publishes churn "
                    f"param_version faster than the rows are re-read "
                    f"(each version drop invalidates the key's whole "
                    f"cache); raise the cache rows/TTL or batch pushes "
                    f"into fewer rounds (docs/sparse-embedding.md)"),
        "evidence": {"hit_rate_history": rates,
                     "pull_bytes_history": pull_bytes,
                     "windows": need,
                     "hit_floor": floor}}]


def _r_barrier_stall(ctx: RuleCtx) -> List[dict]:
    trips = ctx.delta("bps_transport_watchdog_trips")
    barrier = ctx.events("barrier_timeout")
    stall = ctx.events("stall")
    if trips <= 0 and barrier <= 0 and stall <= 0:
        return []
    return [{"subject": "stall",
             "message": (f"progress stalled this window "
                         f"(watchdog trips {trips:.0f}, stall events "
                         f"{stall}, barrier timeouts {barrier}): a round "
                         f"or barrier stopped advancing — check the "
                         f"watchdog dump for the blocked keys and "
                         f"whether a peer is gone vs slow"),
             "evidence": {"watchdog_trips": trips, "stall_events": stall,
                          "barrier_timeouts": barrier}}]


def _r_device_fallback(ctx: RuleCtx) -> List[dict]:
    """The silent-CPU class, live: the devprof sentinel (re-probed every
    window roll) convicted a platform fallback — either the jax backend
    initialized as something other than the intended
    BYTEPS_TPU_DEVICE_PLATFORM, or the probe itself errored (the backend
    raised mid-run / jax-internals drift).  Gauge-snapshot law: fires
    from the FIRST window carrying a convicting probe; quiet whenever
    the summary has no device section (devprof unarmed, or an offline
    replay of a pre-devprof bundle)."""
    probe = (ctx.cur.get("device") or {}).get("probe") or {}
    if not probe.get("fallback"):
        return []
    platform = str(probe.get("platform", "unknown"))
    intended = str(probe.get("intended", "") or "")
    reason = str(probe.get("reason", "") or "") or \
        f"backend initialized as {platform!r}"
    return [{"subject": "device",
             "message": (f"device sentinel convicted a fallback: {reason}"
                         f" — every step since is computing on the wrong "
                         f"platform while the wire metrics read healthy"),
             "evidence": {"platform": platform,
                          "intended": intended,
                          "reason": reason}}]


def _wire_seconds(window: dict) -> float:
    """Summed wire-side seconds (queue + push RTT) across a window's
    keys — the 'is the wire flat?' input to mfu_regression."""
    total = 0.0
    for rec in (window.get("keys") or {}).values():
        comps = rec.get("components") or {}
        total += float(comps.get("queue") or 0.0) \
            + float(comps.get("push_wire") or 0.0)
    return total


def _r_mfu_regression(ctx: RuleCtx) -> List[dict]:
    """Windowed MFU dropped > mfu_regress_frac vs the previous window
    while the wire stayed flat — a DEVICE-side slowdown (throttling, a
    sick chip, a pathological recompilation) that no wire rule can see:
    the round keeps completing, just slower, and the wire components
    barely move.  Consecutive-window rule over the device sections the
    summaries carry, so the offline bundle replay fires identically.
    Quiet unless BOTH windows carry a positive MFU sample (devprof
    armed AND cost_analysis reporting), and quiet when wire seconds
    grew past the flat tolerance — a congested wire also depresses MFU,
    and that story belongs to the wire rules."""
    cur_dev = ctx.cur.get("device") or {}
    prev_dev = ctx.prev.get("device") or {}
    cur_mfu = cur_dev.get("mfu")
    prev_mfu = prev_dev.get("mfu")
    if not isinstance(cur_mfu, (int, float)) \
            or not isinstance(prev_mfu, (int, float)) or prev_mfu <= 0.0:
        return []
    frac = float(ctx.th["mfu_regress_frac"])
    # The 1e-9 absolute slack keeps "exactly at the threshold" on the
    # quiet side of the f32/f64 rounding of prev_mfu * (1 - frac).
    if cur_mfu >= prev_mfu * (1.0 - frac) - 1e-9:
        return []
    cur_wire = _wire_seconds(ctx.cur)
    prev_wire = _wire_seconds(ctx.prev)
    flat = float(ctx.th["mfu_wire_flat_frac"])
    if cur_wire > prev_wire * (1.0 + flat) + 1e-9:
        return []   # the wire grew too: not a device regression
    drop = 1.0 - cur_mfu / prev_mfu
    return [{"subject": "device",
             "message": (f"MFU dropped {drop:.0%} in one window "
                         f"({prev_mfu:.3f} -> {cur_mfu:.3f}) with wire "
                         f"seconds flat ({prev_wire:.3f}s -> "
                         f"{cur_wire:.3f}s): the device itself slowed "
                         f"down — check for thermal throttling, a "
                         f"preempted/shared chip, or an unexpected "
                         f"recompilation (bps.get_device_profile() has "
                         f"the step history)"),
             "evidence": {"mfu": float(cur_mfu),
                          "prev_mfu": float(prev_mfu),
                          "drop_frac": round(drop, 4),
                          "wire_s": round(cur_wire, 4),
                          "prev_wire_s": round(prev_wire, 4)}}]


RULES: List[Rule] = [
    Rule("persistent_straggler", SEV_WARN,
         "one worker trails the lead for consecutive windows",
         _r_persistent_straggler),
    Rule("round_lag_growth", SEV_ERROR,
         "a worker's round lag grows every window",
         _r_round_lag_growth),
    Rule("lane_credit_imbalance", SEV_WARN,
         "one data lane carries nearly all of a server's bytes",
         _r_lane_credit_imbalance),
    Rule("recv_pool_miss_rate", SEV_WARN,
         "receive-buffer pool misses dominate checkouts",
         _r_recv_pool_miss_rate),
    Rule("fusion_dilution", SEV_WARN,
         "fusion buckets ship on the deadline instead of full",
         _r_fusion_dilution),
    Rule("server_hot_shard", SEV_WARN,
         "one PS server carries an outsized keys x bytes load",
         _r_server_hot_shard),
    Rule("nonfinite_gradients", SEV_CRITICAL,
         "NaN/Inf gradient samples appeared",
         _r_nonfinite_gradients),
    Rule("audit_mismatch", SEV_CRITICAL,
         "the consistency auditor saw divergent or lost rounds",
         _r_audit_mismatch),
    Rule("barrier_stall", SEV_ERROR,
         "a round or barrier stopped advancing",
         _r_barrier_stall),
    Rule("tuner_thrash", SEV_WARN,
         "the adaptive-compression tuner keeps flipping a key's codec",
         _r_tuner_thrash),
    Rule("knob_thrash", SEV_WARN,
         "the global knob table keeps switching instead of converging",
         _r_knob_thrash),
    Rule("param_version_stall", SEV_ERROR,
         "a server-resident optimizer key stopped publishing updates",
         _r_param_version_stall),
    Rule("embedding_cache_thrash", SEV_WARN,
         "the embedding hot-row cache stopped absorbing lookups",
         _r_embedding_cache_thrash),
    Rule("replication_lag", SEV_WARN,
         "a server's chain replication trails its publishes",
         _r_replication_lag),
    Rule("device_fallback", SEV_CRITICAL,
         "the device sentinel convicted a platform fallback or probe error",
         _r_device_fallback),
    Rule("mfu_regression", SEV_WARN,
         "windowed MFU dropped sharply while the wire stayed flat",
         _r_mfu_regression),
]

# ---------------------------------------------------------------------------
# Fleet plane (docs/monitoring.md "Fleet plane"): publish-doc builder,
# view alignment, and the fleet rule set — rules over the MERGED
# per-worker window view the CMD_WINDOW/CMD_FLEET wire serves.  Same
# Rule/Finding/playbook machinery as the local rules; the windows a
# fleet RuleCtx sees are ALIGNED fleet windows (one entry per window
# index, every worker's published row preserved), so live
# (bps.get_fleet / bps_doctor --fleet) and offline (merged postmortem
# bundles) verdicts are identical by construction.
# ---------------------------------------------------------------------------

FLEET_SCHEMA = "bps-fleet-window-v1"


def fleet_publish_doc(summary: dict, worker_id: int,
                      clock: Optional[dict] = None,
                      open_findings=(),
                      codecs: Optional[dict] = None) -> dict:
    """The compact per-worker slice CMD_WINDOW ships at each window
    roll: per-key KeySignal slices (class / wire_mbps / component
    seconds), summed critical-path component seconds, straggler blame
    (this worker's max-round-lag view), the clock-offset estimate vs
    its rank-0 server, open doctor finding ids, and — when the summary
    carried a CMD_STATS refresh — per-server byte rows (what the
    fleet-fed autoscaler consumes).  Deliberately NOT the full summary:
    the metrics snapshot alone can be tens of KB, and the fleet law is
    one SMALL frame per worker per window."""
    metrics = summary.get("metrics") or {}
    lag: Dict[str, int] = {}
    for labels, v in parse_series(metrics, "bps_worker_round_lag").items():
        d = dict(labels)
        if "worker" in d:
            lag[str(d["worker"])] = int(v)
    blame = None
    if lag:
        worst = max(lag, key=lambda k: lag[k])
        if lag[worst] > 0:
            blame = {"worker": worst, "lag": lag[worst]}
    keys: Dict[str, dict] = {}
    comp_total: Dict[str, float] = {}
    for label, rec in (summary.get("keys") or {}).items():
        comps = {k: float(v or 0.0)
                 for k, v in (rec.get("components") or {}).items()}
        keys[label] = {"class": rec.get("class"),
                       "wire_mbps": float(rec.get("wire_mbps") or 0.0),
                       "components": comps}
        for c, v in comps.items():
            comp_total[c] = comp_total.get(c, 0.0) + v
    # Devprof plane (PR 20): measured on-device seconds ride as their
    # own component (the goodput ledger's measured `compute` input —
    # per-key components are wire-side only, so this never collides),
    # and mfu / device_platform ride top-level so worker 0 can convict
    # a slow-chip worker whose MFU lags the quorum.
    dev = summary.get("device") or {}
    dev_s = float(dev.get("compute_s") or 0.0)
    if dev_s > 0.0:
        comp_total["device_compute"] = \
            comp_total.get("device_compute", 0.0) + dev_s
    doc = {
        "schema": FLEET_SCHEMA,
        "window": summary.get("window"),
        "ts": summary.get("ts"),
        "mono": summary.get("mono"),
        "anchor": summary.get("anchor"),
        "dur_s": float(summary.get("dur_s") or 0.0),
        "worker": int(worker_id),
        "keys": keys,
        "components": comp_total,
        "events": dict(summary.get("events") or {}),
        "lag": lag,
        "blame": blame,
        "clock_offset_us": (float(clock["offset_us"])
                            if clock and isinstance(
                                clock.get("offset_us"),
                                (int, float)) else None),
        "findings": sorted(set(open_findings)),
    }
    if dev:
        doc["mfu"] = dev.get("mfu")
        doc["device_platform"] = dev.get("platform")
    if codecs:
        doc["codecs"] = {
            str(label): {"name": c.get("name"),
                         "epoch": int(c.get("epoch", 0)),
                         "pending": bool(c.get("pending"))}
            for label, c in codecs.items() if isinstance(c, dict)}
    rows = (summary.get("server") or {}).get("servers") or {}
    servers = {str(sid): {"alive": bool(row.get("alive")),
                          "draining": bool(row.get("draining")),
                          "bytes_in": int(row.get("bytes_in", 0)),
                          "bytes_out": int(row.get("bytes_out", 0))}
               for sid, row in rows.items() if isinstance(row, dict)}
    if servers:
        doc["servers"] = servers
    return doc


def fleet_windows_from_view(view: dict) -> List[dict]:
    """ALIGN a merged CMD_FLEET view ({"workers": {wid: [doc, ...]}})
    into the fleet-window stream the fleet rules consume: one entry per
    window index present in ANY worker's ring, oldest..newest, each
    carrying every worker's row for that index.  Alignment is by the
    explicit window index the summaries publish (never poll timing), so
    a joiner appears the first window it publishes and an evicted
    worker's expired ring simply stops contributing rows."""
    by_idx: Dict[int, Dict[int, dict]] = {}
    for wid, rows in (view.get("workers") or {}).items():
        for row in rows or ():
            if not isinstance(row, dict) or "window" not in row:
                continue
            try:
                idx = int(row["window"])
            except (TypeError, ValueError):
                continue
            by_idx.setdefault(idx, {})[int(wid)] = row
    out = []
    for idx in sorted(by_idx):
        workers = by_idx[idx]
        ts = max((float(r.get("ts") or 0.0)
                  for r in workers.values()), default=0.0)
        out.append({"schema": FLEET_SCHEMA, "window": idx, "ts": ts,
                    "workers": workers, "n_workers": len(workers)})
    return out


def fleet_view_from_bundles(bundles: List[dict]) -> dict:
    """Reconstruct the fleet view offline from postmortem bundles: each
    bundle's ``extra.fleet.published`` list is that worker's ring (the
    exact docs its CMD_WINDOW frames carried), so merging them per
    (worker, window) rebuilds what CMD_FLEET would have served —
    identical verdicts by construction."""
    by_idx: Dict[int, Dict[int, dict]] = {}
    for b in bundles:
        sec = ((b.get("extra") or {}).get("fleet") or {})
        for row in sec.get("published") or ():
            if not isinstance(row, dict) or "window" not in row:
                continue
            try:
                wid = int(row.get("worker", b.get("rank", -1)))
                idx = int(row["window"])
            except (TypeError, ValueError):
                continue
            by_idx.setdefault(wid, {}).setdefault(idx, row)
    return {"armed": bool(by_idx),
            "workers": {wid: [ring[i] for i in sorted(ring)]
                        for wid, ring in by_idx.items()}}


def _fleet_quorum(n_views: int, frac: float) -> int:
    """Votes needed for "the same worker in >= quorum of views": at
    least ceil(frac * n) and never less than 2 — one worker blaming
    itself alone must not confirm a fleet-level verdict."""
    need = int(frac * n_views)
    if need < frac * n_views:
        need += 1
    return max(2, need)


def _fr_straggler_confirmed(ctx: RuleCtx) -> List[dict]:
    need = int(ctx.th["fleet_straggler_windows"])
    if len(ctx.windows) < need:
        return []
    min_lag = int(ctx.th["fleet_straggler_min_lag"])
    confirmed_per_window = []
    for w in ctx.windows[-need:]:
        workers = w.get("workers") or {}
        if len(workers) < 2:
            return []
        votes: Dict[str, int] = {}
        for doc in workers.values():
            b = doc.get("blame") or {}
            if b.get("worker") is not None \
                    and int(b.get("lag", 0)) >= min_lag:
                wid = str(b["worker"])
                votes[wid] = votes.get(wid, 0) + 1
        quorum = _fleet_quorum(len(workers),
                               float(ctx.th["fleet_quorum_frac"]))
        confirmed_per_window.append(
            ({w2 for w2, n in votes.items() if n >= quorum},
             votes, len(workers)))
    persist = set.intersection(
        *[c for c, _, _ in confirmed_per_window])
    out = []
    last_votes, last_n = (confirmed_per_window[-1][1],
                          confirmed_per_window[-1][2])
    for wid in sorted(persist):
        out.append({
            "subject": f"worker {wid}",
            "message": (f"worker {wid} is max round-lag blame in "
                        f"{last_votes.get(wid, 0)}/{last_n} workers' "
                        f"fleet views for {need} consecutive windows — "
                        f"a fleet-confirmed straggler, not one view's "
                        f"opinion"),
            "evidence": {"worker": wid,
                         "votes": last_votes.get(wid, 0),
                         "views": last_n, "windows": need},
        })
    return out


def _fr_clock_skew(ctx: RuleCtx) -> List[dict]:
    need = int(ctx.th["clock_skew_windows"])
    if len(ctx.windows) < need:
        return []
    limit_us = float(ctx.th["clock_skew_ms"]) * 1000.0
    persist: Optional[set] = None
    last_detail: Dict[str, tuple] = {}
    for w in ctx.windows[-need:]:
        offs = {}
        for wid, doc in (w.get("workers") or {}).items():
            v = doc.get("clock_offset_us")
            if isinstance(v, (int, float)):
                offs[str(wid)] = float(v)
        if len(offs) < 2:
            return []
        vals = sorted(offs.values())
        mid = len(vals) // 2
        median = (vals[mid] if len(vals) % 2
                  else (vals[mid - 1] + vals[mid]) / 2.0)
        offenders = {wid for wid, v in offs.items()
                     if abs(v - median) > limit_us}
        last_detail = {wid: (offs[wid], median) for wid in offenders}
        persist = offenders if persist is None else (persist & offenders)
    out = []
    for wid in sorted(persist or ()):
        off, median = last_detail.get(wid, (0.0, 0.0))
        out.append({
            "subject": f"worker {wid}",
            "message": (f"worker {wid}'s clock-offset estimate "
                        f"({off / 1000.0:.1f} ms) drifts "
                        f"{abs(off - median) / 1000.0:.1f} ms from the "
                        f"fleet median ({median / 1000.0:.1f} ms) for "
                        f"{need} consecutive windows — its timestamps "
                        f"cannot be merged onto the fleet timeline"),
            "evidence": {"worker": wid, "offset_us": off,
                         "median_us": median, "limit_ms":
                         float(ctx.th["clock_skew_ms"])},
        })
    return out


def _fr_codec_epoch_divergence(ctx: RuleCtx) -> List[dict]:
    need = int(ctx.th["codec_divergence_windows"])
    if len(ctx.windows) < need:
        return []
    persist: Optional[set] = None
    last_detail: Dict[str, dict] = {}
    for w in ctx.windows[-need:]:
        divergent = set()
        by_key: Dict[str, Dict[int, dict]] = {}
        for wid, doc in (w.get("workers") or {}).items():
            for label, c in (doc.get("codecs") or {}).items():
                if isinstance(c, dict) and not c.get("pending"):
                    by_key.setdefault(str(label), {})[int(wid)] = c
        for label, views in by_key.items():
            if len(views) < 2:
                continue
            # Server-authoritative law: one epoch maps to ONE codec.
            # Workers at the SAME epoch with different active names,
            # none pending, have forked wire formats.
            by_epoch: Dict[int, set] = {}
            for c in views.values():
                by_epoch.setdefault(int(c.get("epoch", 0)), set()).add(
                    str(c.get("name")))
            names = next((ns for ns in by_epoch.values() if len(ns) > 1),
                         None)
            if names:
                divergent.add(label)
                last_detail[label] = {
                    "names": sorted(names),
                    "workers": sorted(views)}
        persist = divergent if persist is None else (persist & divergent)
    out = []
    for label in sorted(persist or ()):
        d = last_detail.get(label, {})
        out.append({
            "subject": f"key {label}",
            "message": (f"workers {d.get('workers')} report the same "
                        f"codec epoch for key {label} but different "
                        f"active codecs {d.get('names')} past the "
                        f"declared boundary for {need} consecutive "
                        f"windows — the wire formats have forked"),
            "evidence": {"key": label, **d, "windows": need},
        })
    return out


def _fr_signal_disagreement(ctx: RuleCtx) -> List[dict]:
    w = ctx.cur
    workers = w.get("workers") or {}
    if len(workers) < 2:
        return []
    ratio = float(ctx.th["signal_spread_ratio"])
    floor = float(ctx.th["signal_min_mbps"])
    per_key: Dict[str, Dict[str, float]] = {}
    for wid, doc in workers.items():
        for label, rec in (doc.get("keys") or {}).items():
            mbps = float(rec.get("wire_mbps") or 0.0)
            per_key.setdefault(str(label), {})[str(wid)] = mbps
    out = []
    for label in sorted(per_key):
        views = per_key[label]
        if len(views) < 2:
            continue
        hi_w = max(views, key=lambda k: views[k])
        lo_w = min(views, key=lambda k: views[k])
        hi, lo = views[hi_w], views[lo_w]
        if hi >= floor and hi > lo * ratio:
            out.append({
                "subject": f"key {label}",
                "message": (f"key {label}'s wire_mbps spreads "
                            f"{hi:.1f} (worker {hi_w}) vs {lo:.1f} "
                            f"(worker {lo_w}) across workers (> "
                            f"{ratio:g}x) — per-worker bandwidth "
                            f"samples disagree, so a single worker's "
                            f"tuner view is flying blind"),
                "evidence": {"key": label, "max_mbps": hi,
                             "min_mbps": lo, "max_worker": hi_w,
                             "min_worker": lo_w, "ratio": ratio},
            })
    return out


FLEET_RULES: List[Rule] = [
    Rule("fleet_straggler_confirmed", SEV_ERROR,
         "the same worker is max-blame in a quorum of fleet views",
         _fr_straggler_confirmed),
    Rule("clock_skew", SEV_WARN,
         "a worker's clock-offset estimate drifts from the fleet median",
         _fr_clock_skew),
    Rule("codec_epoch_divergence", SEV_ERROR,
         "workers disagree on a key's active codec past the boundary",
         _fr_codec_epoch_divergence),
    Rule("signal_disagreement", SEV_WARN,
         "a key's per-worker wire_mbps spread exceeds the tuner's trust",
         _fr_signal_disagreement),
]

# Every rule id — local AND fleet — carries a playbook anchor
# (check_doctor_docs pins both directions).
RULE_IDS = tuple(r.id for r in RULES) + tuple(r.id for r in FLEET_RULES)


def evaluate_fleet_stream(fleet_windows: List[dict],
                          thresholds: Optional[dict] = None,
                          history: int = 8) -> dict:
    """Offline fleet evaluation: replay ALIGNED fleet windows (from
    ``fleet_windows_from_view``) through a silent engine running the
    fleet rule set.  The one entry point ``tools/bps_doctor.py --fleet``
    and ``tools/postmortem.py`` use for merged bundles — live/offline
    parity by construction (the live /fleet route evaluates the same
    aligned stream)."""
    eng = DoctorEngine(rules=FLEET_RULES, thresholds=thresholds,
                       history=history, emit=False)
    for w in fleet_windows:
        eng.observe(w)
    diag = eng.diagnosis()
    diag["windows_evaluated"] = len(fleet_windows)
    diag["fleet"] = True
    return diag


class DoctorEngine:
    """Evaluates the rule set against each closing window.

    Findings are identity-keyed by (rule, subject): a condition that
    persists across windows stays ONE open finding (evidence refreshed,
    logged once); a condition that stops firing closes.  ``emit=False``
    turns off the side effects (log/flightrec/counter) — the offline
    replay mode ``tools/bps_doctor.py`` uses, so live and offline runs
    of the same rules differ only in plumbing."""

    def __init__(self, rules: Optional[List[Rule]] = None,
                 thresholds: Optional[dict] = None,
                 history: int = 8, emit: bool = True):
        self.rules = list(rules if rules is not None else RULES)
        self.thresholds = dict(thresholds or {})
        self.emit = emit
        self._lock = threading.Lock()
        self._windows: deque = deque(maxlen=max(2, int(history)))
        self._open: Dict[tuple, dict] = {}
        # Recent findings OPENED (bounded: a finding flapping at a rule
        # threshold every window must not grow memory for the life of a
        # multi-day job) + the lifetime open count.
        self._all: deque = deque(maxlen=200)
        self._total_opened = 0
        self._last_window = -1
        self._last_ts = 0.0

    # -- evaluation ---------------------------------------------------------
    def observe(self, summary: dict) -> List[dict]:
        """Fold one window summary in; returns the findings that fired
        this window (open + newly opened)."""
        with self._lock:
            self._windows.append(summary)
            ctx = RuleCtx(list(self._windows), self.thresholds)
            self._last_window = int(summary.get("window", -1))
            self._last_ts = float(summary.get("ts", time.time()))
            fired: List[dict] = []
            seen: set = set()
            for rule in self.rules:
                try:
                    hits = rule.fn(ctx) or []
                except Exception:
                    get_logger().exception("doctor rule %r failed",
                                           rule.id)
                    # A crashed rule says NOTHING about its condition:
                    # keep its open findings open (closing them here
                    # would re-open them next window as fresh findings
                    # — double-logged, double-counted, identity reset).
                    for key in self._open:
                        if key[0] == rule.id:
                            seen.add(key)
                    continue
                for hit in hits:
                    key = (rule.id, hit.get("subject", ""))
                    seen.add(key)
                    prior = self._open.get(key)
                    finding = {
                        "rule": rule.id,
                        "severity": hit.get("severity", rule.severity),
                        "subject": hit.get("subject", ""),
                        "summary": hit.get("message", rule.summary),
                        "evidence": hit.get("evidence", {}),
                        "playbook": playbook_anchor(rule.id),
                        "window": self._last_window,
                        "first_window": (prior["first_window"] if prior
                                         else self._last_window),
                        "ts": self._last_ts,
                    }
                    self._open[key] = finding
                    fired.append(finding)
                    if prior is None:
                        self._all.append(finding)
                        self._total_opened += 1
                        if self.emit:
                            self._emit_new(finding)
            closed = [k for k in self._open if k not in seen]
            for k in closed:
                f = self._open.pop(k)
                if self.emit:
                    get_logger().info(
                        "bps doctor: %s (%s) cleared after window %d",
                        f["rule"], f["subject"], self._last_window)
            return fired

    def _emit_new(self, f: dict) -> None:
        log = get_logger()
        line = (f"bps doctor [{f['severity'].upper()}] {f['rule']} "
                f"({f['subject']}): {f['summary']}  -> see {f['playbook']}")
        if f["severity"] == SEV_WARN:
            log.warning(line)
        else:
            log.error(line)
        try:
            from . import telemetry
            telemetry.get_registry().counter(
                "bps_doctor_findings_total",
                help="doctor findings opened, by rule",
                labels={"rule": f["rule"]}).inc()
        except Exception:
            pass
        try:
            from . import flightrec
            flightrec.record("doctor_finding", rule=f["rule"],
                             severity=f["severity"],
                             subject=f["subject"],
                             summary=f["summary"],
                             playbook=f["playbook"],
                             window=f["window"])
        except Exception:
            pass

    # -- read surfaces ------------------------------------------------------
    def diagnosis(self) -> dict:
        """The ``bps.get_diagnosis()`` payload."""
        with self._lock:
            open_f = sorted(
                self._open.values(),
                key=lambda f: (-_SEV_ORDER.get(f["severity"], 0),
                               f["rule"], f["subject"]))
            return {"armed": True,
                    "window": self._last_window,
                    "ts": self._last_ts,
                    "healthy": not open_f,
                    "open": [dict(f) for f in open_f],
                    "findings_total": self._total_opened,
                    "history": [dict(f)
                                for f in list(self._all)[-50:]]}

    def verdict_line(self) -> str:
        """One-line shutdown/atexit verdict."""
        with self._lock:
            if not self._open:
                seen = self._total_opened
                return ("bps doctor: healthy — no open findings"
                        + (f" ({seen} cleared during the run)"
                           if seen else ""))
            parts = [f"{f['rule']}({f['subject']})"
                     for f in self._open.values()]
            return (f"bps doctor: {len(self._open)} open finding(s) at "
                    f"shutdown: {', '.join(sorted(parts))} — see "
                    f"{PLAYBOOK}")


def evaluate_stream(summaries: List[dict],
                    thresholds: Optional[dict] = None,
                    history: int = 8) -> dict:
    """Offline evaluation: replay window summaries through a silent
    engine (identical rules, no side effects) and return its final
    diagnosis plus every finding opened along the way.  This is the one
    entry point ``tools/bps_doctor.py`` uses for bundles and metrics
    JSONLs — live/offline parity is by construction."""
    eng = DoctorEngine(thresholds=thresholds, history=history, emit=False)
    for s in summaries:
        eng.observe(s)
    diag = eng.diagnosis()
    diag["windows_evaluated"] = len(summaries)
    return diag


def summaries_from_metrics_jsonl(lines: List[dict]) -> List[dict]:
    """Window summaries from metrics-JSONL snapshot lines
    ({"ts", "metrics"} — the BYTEPS_TPU_METRICS_LOG format).  Each line
    becomes one window: scalars only (rules ignore histogram dicts),
    no per-key signal records or flight events — the rules that need
    those simply stay quiet, and a live doctor over the same stream
    agrees (parity-tested)."""
    out = []
    prev_ts: Optional[float] = None
    for i, line in enumerate(lines):
        metrics = {k: v for k, v in (line.get("metrics") or {}).items()
                   if isinstance(v, (int, float))}
        ts = float(line.get("ts", 0.0))
        out.append({"schema": "bps-signal-window-v1", "window": i,
                    "ts": ts, "dur_s": (ts - prev_ts) if prev_ts else 0.0,
                    "keys": {}, "metrics": metrics, "events": {}})
        prev_ts = ts
    return out
