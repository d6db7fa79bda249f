"""Doctor rule-engine tests (common/doctor.py, ISSUE 12): every rule's
fire/no-fire boundary on synthetic window summaries, finding open/close
identity + side-effect feeds, live-vs-offline parity over a recorded
metrics JSONL, and the postmortem-bundle diagnosis section.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from byteps_tpu.common import doctor, flightrec
from byteps_tpu.common import telemetry as tm

TOOLS = os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "tools")
if TOOLS not in sys.path:
    sys.path.insert(0, TOOLS)


def W(idx=0, metrics=None, events=None, **sections):
    """One synthetic window summary."""
    s = {"schema": "bps-signal-window-v1", "window": idx,
         "ts": 1000.0 + idx * 10.0, "dur_s": 10.0, "keys": {},
         "metrics": metrics or {}, "events": events or {}}
    s.update(sections)
    return s


def rules_fired(windows, **thresholds):
    diag = doctor.evaluate_stream(windows, thresholds=thresholds or None)
    return {f["rule"] for f in diag["history"]}


def lag(w0, w1):
    return {'bps_worker_round_lag{worker="0"}': w0,
            'bps_worker_round_lag{worker="1"}': w1}


# ---------------------------------------------------------------------------
# Per-rule fire / no-fire boundaries
# ---------------------------------------------------------------------------
def test_persistent_straggler_boundary():
    # Fires: worker 1 is the max-lag worker (lag >= 1) for 2 windows.
    hot = [W(0, lag(0, 2)), W(1, lag(0, 2))]
    assert "persistent_straggler" in rules_fired(hot)
    diag = doctor.evaluate_stream(hot)
    f = next(x for x in diag["open"]
             if x["rule"] == "persistent_straggler")
    assert f["subject"] == "worker=1"           # names the slow worker
    assert f["evidence"]["worker"] == "1"
    assert f["playbook"].endswith("#rule-persistent_straggler")
    # One window is not persistent.
    assert "persistent_straggler" not in rules_fired([W(0, lag(0, 2))])
    # Everyone in step: quiet.
    assert "persistent_straggler" not in rules_fired(
        [W(0, lag(0, 0)), W(1, lag(0, 0))])
    # The straggler identity must be STABLE across the windows.
    assert "persistent_straggler" not in rules_fired(
        [W(0, lag(2, 0)), W(1, lag(0, 2))])


def test_round_lag_growth_boundary():
    grow = [W(i, lag(0, i + 1)) for i in range(3)]       # 1, 2, 3
    assert "round_lag_growth" in rules_fired(grow)
    flat = [W(i, lag(0, 2)) for i in range(3)]           # behind, stable
    assert "round_lag_growth" not in rules_fired(flat)
    two = [W(i, lag(0, i + 1)) for i in range(2)]        # too short
    assert "round_lag_growth" not in rules_fired(two)


def _lanes(b0, b1):
    return {"lanes": [
        {"server": 0, "lane": 0, "bytes_total": b0, "sends": 1},
        {"server": 0, "lane": 1, "bytes_total": b1, "sends": 1}]}


def test_lane_credit_imbalance_boundary():
    hot = [W(0, transport=_lanes(0, 0)),
           W(1, transport=_lanes(90 << 20, 1 << 20))]
    assert "lane_credit_imbalance" in rules_fired(hot)
    even = [W(0, transport=_lanes(0, 0)),
            W(1, transport=_lanes(45 << 20, 40 << 20))]
    assert "lane_credit_imbalance" not in rules_fired(even)
    quiet = [W(0, transport=_lanes(0, 0)),
             W(1, transport=_lanes(900, 10))]   # under the traffic floor
    assert "lane_credit_imbalance" not in rules_fired(quiet)
    # Lifetime-counter law: an OLD skew that stopped (no in-window
    # delta) must not keep the finding alive — and a fresh wedge after
    # hours of balance must fire on the window's delta alone.
    old_skew = [W(0, transport=_lanes(90 << 20, 1 << 20)),
                W(1, transport=_lanes(90 << 20, 1 << 20))]
    assert "lane_credit_imbalance" not in rules_fired(old_skew)
    late_wedge = [W(0, transport=_lanes(500 << 20, 500 << 20)),
                  W(1, transport=_lanes(590 << 20, (500 << 20) + 4096))]
    assert "lane_credit_imbalance" in rules_fired(late_wedge)
    # First window (no baseline) and JSONL replay (no lanes): quiet.
    assert "lane_credit_imbalance" not in rules_fired(
        [W(0, transport=_lanes(90 << 20, 1 << 20))])
    assert "lane_credit_imbalance" not in rules_fired([W(0), W(1)])


def test_recv_pool_miss_rate_boundary():
    def m(hits, misses):
        return {"bps_transport_pool_hits": hits,
                "bps_transport_pool_misses": misses}
    hot = [W(0, m(0, 0)), W(1, m(10, 90))]      # 90% misses in-window
    assert "recv_pool_miss_rate" in rules_fired(hot)
    ok = [W(0, m(0, 0)), W(1, m(90, 10))]
    assert "recv_pool_miss_rate" not in rules_fired(ok)
    few = [W(0, m(0, 0)), W(1, m(1, 9))]        # under the event floor
    assert "recv_pool_miss_rate" not in rules_fired(few)
    # Counter-delta law: a HIGH cumulative total with no in-window
    # activity must not fire (gauge-style reads would).
    idle = [W(0, m(10, 90)), W(1, m(10, 90))]
    assert "recv_pool_miss_rate" not in rules_fired(idle)


def test_fusion_dilution_boundary():
    def m(deadline, full):
        return {"bps_fusion_deadline_flushes": deadline,
                "bps_fusion_full_flushes": full}
    hot = [W(0, m(0, 0)), W(1, m(9, 1))]
    assert "fusion_dilution" in rules_fired(hot)
    ok = [W(0, m(0, 0)), W(1, m(2, 8))]
    assert "fusion_dilution" not in rules_fired(ok)
    few = [W(0, m(0, 0)), W(1, m(2, 0))]        # under the flush floor
    assert "fusion_dilution" not in rules_fired(few)


def test_server_hot_shard_boundary():
    def owned(a, b, c):
        return {'bps_keys_owned{server="0"}': a,
                'bps_keys_owned{server="1"}': b,
                'bps_keys_owned{server="2"}': c}
    hot = [W(0, owned(30, 3, 3))]
    assert "server_hot_shard" in rules_fired(hot)
    diag = doctor.evaluate_stream(hot)
    f = next(x for x in diag["open"] if x["rule"] == "server_hot_shard")
    assert f["subject"] == "server=0"
    even = [W(0, owned(12, 12, 12))]
    assert "server_hot_shard" not in rules_fired(even)
    tiny = [W(0, owned(4, 1, 1))]               # under the key floor
    assert "server_hot_shard" not in rules_fired(tiny)
    # keys_owned x bytes weighting: the BYTE-heavy server (in-window
    # bytes_in DELTA — the counter is lifetime) is the hot one even
    # when key counts alone look tolerable.
    def srv(b0, b1, b2):
        return {"servers": {"0": {"bytes_in": b0},
                            "1": {"bytes_in": b1},
                            "2": {"bytes_in": b2}}}
    weighted = [W(0, owned(10, 10, 10), server=srv(0, 0, 0)),
                W(1, owned(10, 10, 10),
                  server=srv(95 << 20, 1 << 20, 1 << 20))]
    diag = doctor.evaluate_stream(weighted)
    f = next(x for x in diag["open"] if x["rule"] == "server_hot_shard")
    assert f["subject"] == "server=0"
    assert f["evidence"]["basis"] == "keys_owned x bytes_in"
    # A PARTIAL server section (one server's row missing — e.g. it was
    # momentarily unreachable) must fall back to keys_owned, not zero
    # the missing server's load and crown a balanced server "hot".
    partial = [W(0, owned(10, 10, 10), server=srv(0, 0, 0)),
               W(1, owned(10, 10, 10),
                 server={"servers": {"0": {"bytes_in": 95 << 20}}})]
    assert "server_hot_shard" not in rules_fired(partial)


def test_nonfinite_and_audit_boundaries():
    hot = [W(0, {"bps_grad_nonfinite_total": 0}),
           W(1, {"bps_grad_nonfinite_total": 2,
                 'bps_grad_nonfinite{key="g.w"}': 4})]
    diag = doctor.evaluate_stream(hot)
    f = next(x for x in diag["open"]
             if x["rule"] == "nonfinite_gradients")
    assert f["severity"] == "critical"
    assert f["evidence"]["keys"] == ["g.w"]
    assert "nonfinite_gradients" not in rules_fired(
        [W(0, {"bps_grad_nonfinite_total": 2}),
         W(1, {"bps_grad_nonfinite_total": 2})])    # stale total: quiet

    assert "audit_mismatch" in rules_fired(
        [W(0, {"bps_audit_mismatch_total": 0}),
         W(1, {"bps_audit_mismatch_total": 1})])
    assert "audit_mismatch" in rules_fired(
        [W(0, {"bps_audit_round_skew_total": 0}),
         W(1, {"bps_audit_round_skew_total": 1})])
    assert "audit_mismatch" not in rules_fired(
        [W(0, {"bps_audit_mismatch_total": 0}),
         W(1, {"bps_audit_mismatch_total": 0})])


def test_barrier_stall_boundary():
    assert "barrier_stall" in rules_fired(
        [W(0, events={"barrier_timeout": 1})])
    assert "barrier_stall" in rules_fired(
        [W(0, events={"stall": 2})])
    assert "barrier_stall" in rules_fired(
        [W(0, {"bps_transport_watchdog_trips": 0}),
         W(1, {"bps_transport_watchdog_trips": 1})])
    assert "barrier_stall" not in rules_fired([W(0), W(1)])


def test_tuner_thrash_boundary():
    """Fires when a key's switch counter grew in > N of the last M
    windows; names the key and carries its class history."""
    def sw(v, cls="wire_bound"):
        return {"metrics": {'bps_tuner_key_switches_total{key="k1"}': v},
                "keys": {"k1": {"class": cls}}}

    # 3 switch windows out of 6 (> default 2): fires.
    hot = [W(i, **sw(v)) for i, v in enumerate([0, 1, 2, 3, 3, 3, 3])]
    fired = rules_fired(hot)
    assert "tuner_thrash" in fired
    diag = doctor.evaluate_stream(hot)
    f = next(x for x in diag["history"] if x["rule"] == "tuner_thrash")
    assert f["subject"] == "key=k1"
    assert f["evidence"]["switch_windows"] == 3
    assert "wire_bound" in f["evidence"]["class_history"]
    # Exactly N switch windows: quiet (boundary is strict >).
    warm = [W(i, **sw(v)) for i, v in enumerate([0, 1, 2, 2, 2, 2, 2])]
    assert "tuner_thrash" not in rules_fired(warm)
    # A converged tuner (counter flat): quiet.
    cold = [W(i, **sw(3)) for i in range(7)]
    assert "tuner_thrash" not in rules_fired(cold)
    # Counter restart (delta clamps at 0): quiet.
    reset = [W(0, **sw(5)), W(1, **sw(0)), W(2, **sw(0)),
             W(3, **sw(0)), W(4, **sw(0)), W(5, **sw(0)), W(6, **sw(0))]
    assert "tuner_thrash" not in rules_fired(reset)


def test_knob_thrash_boundary():
    """Fires when the GLOBAL knob table's switch counter grew in > N of
    the last M windows; evidence carries the per-window knob history
    (epoch + live values)."""
    def kn(v, epoch=None, fb=None):
        m = {"bps_knob_switches_total": v}
        if epoch is not None:
            m["bps_knob_epoch"] = epoch
        if fb is not None:
            m['bps_knob_value{knob="fusion_bytes"}'] = fb
        return {"metrics": m}

    # 3 switch windows out of 6 (> default 2): fires, with history.
    hot = [W(i, **kn(v, epoch=v, fb=(1 << 20) * (v + 1)))
           for i, v in enumerate([0, 1, 2, 3, 3, 3, 3])]
    fired = rules_fired(hot)
    assert "knob_thrash" in fired
    diag = doctor.evaluate_stream(hot)
    # The still-open finding carries evidence refreshed to the newest
    # window — the full 6-pair knob history.
    f = next(x for x in diag["open"] if x["rule"] == "knob_thrash")
    assert f["subject"] == "knob_table"
    assert f["evidence"]["switch_windows"] == 3
    assert f["playbook"].endswith("#rule-knob_thrash")
    hist = f["evidence"]["knob_history"]
    assert len(hist) == 6
    assert hist[0]["switched"] is True and hist[-1]["switched"] is False
    assert hist[2]["epoch"] == 3
    assert hist[2]["knobs"]["fusion_bytes"] == (1 << 20) * 4
    # Exactly N switch windows: quiet (boundary is strict >).
    warm = [W(i, **kn(v)) for i, v in enumerate([0, 1, 2, 2, 2, 2, 2])]
    assert "knob_thrash" not in rules_fired(warm)
    # A converged knob plane (counter flat): quiet.
    cold = [W(i, **kn(3)) for i in range(7)]
    assert "knob_thrash" not in rules_fired(cold)
    # Counter restart (delta clamps at 0): quiet.
    reset = [W(0, **kn(5))] + [W(i + 1, **kn(0)) for i in range(6)]
    assert "knob_thrash" not in rules_fired(reset)


def test_param_version_stall_boundary():
    def srv(completed, pv, opt_mode=3):
        return {"server": {"keys": {"7": {
            "completed_round": completed, "param_version": pv,
            "opt_mode": opt_mode}}}}

    # Fires: rounds complete for 2 consecutive windows, param_version
    # frozen — the update stage is wedged.
    stall = [W(0, **srv(4, 4)), W(1, **srv(6, 4)), W(2, **srv(8, 4))]
    fired = rules_fired(stall)
    assert "param_version_stall" in fired
    diag = doctor.evaluate_stream(stall)
    f = next(x for x in diag["open"]
             if x["rule"] == "param_version_stall")
    assert f["subject"] == "key=7"
    assert f["playbook"].endswith("#rule-param_version_stall")
    # Healthy: param_version advances with the rounds.
    ok = [W(0, **srv(4, 4)), W(1, **srv(6, 6)), W(2, **srv(8, 8))]
    assert "param_version_stall" not in rules_fired(ok)
    # One stalled window is not enough (threshold = 2).
    assert "param_version_stall" not in rules_fired(
        [W(0, **srv(4, 4)), W(1, **srv(6, 4))])
    # Idle key (rounds not advancing either): quiet — nothing is wedged,
    # the job just is not training.
    idle = [W(0, **srv(4, 4)), W(1, **srv(4, 4)), W(2, **srv(4, 4))]
    assert "param_version_stall" not in rules_fired(idle)
    # Sum-only keys (opt_mode 0) never fire.
    off = [W(0, **srv(4, 0, 0)), W(1, **srv(6, 0, 0)),
           W(2, **srv(8, 0, 0))]
    assert "param_version_stall" not in rules_fired(off)


def test_embedding_cache_thrash_boundary():
    """Row-sparse lookup tier (ISSUE 17): fires when the hot-row cache
    hit rate sits below the floor for 2 consecutive windows WHILE pull
    bytes grow; quiet on cold/idle readers, healthy hit rates, one bad
    window, or a low rate with no wire traffic."""
    def em(hits, misses, pulled):
        return {"bps_embed_cache_hits": hits,
                "bps_embed_cache_misses": misses,
                "bps_embed_pull_bytes_total": pulled}

    # Fires: ~10% hit rate across two windows, pull bytes growing.
    hot = [W(0, em(10, 90, 1 << 20)), W(1, em(20, 180, 2 << 20)),
           W(2, em(30, 270, 3 << 20))]
    assert "embedding_cache_thrash" in rules_fired(hot)
    diag = doctor.evaluate_stream(hot)
    f = next(x for x in diag["open"]
             if x["rule"] == "embedding_cache_thrash")
    assert f["subject"] == "embed-cache"
    assert f["evidence"]["hit_rate_history"] == [0.1, 0.1]
    assert f["playbook"].endswith("#rule-embedding_cache_thrash")
    # One collapsed window is not thrash (threshold = 2 consecutive).
    assert "embedding_cache_thrash" not in rules_fired(
        [W(0, em(10, 90, 1 << 20)), W(1, em(20, 180, 2 << 20))])
    # Healthy hit rate: quiet (zipf head absorbed client-side).
    ok = [W(0, em(900, 100, 1 << 20)), W(1, em(1800, 200, 2 << 20)),
          W(2, em(2700, 300, 3 << 20))]
    assert "embedding_cache_thrash" not in rules_fired(ok)
    # Low rate but NO pull-byte growth: not thrash (nothing pays wire).
    flat = [W(0, em(10, 90, 1 << 20)), W(1, em(20, 180, 1 << 20)),
            W(2, em(30, 270, 1 << 20))]
    assert "embedding_cache_thrash" not in rules_fired(flat)
    # Cold/idle reader below the per-window lookup floor: quiet.
    idle = [W(0, em(1, 9, 1 << 10)), W(1, em(2, 18, 2 << 10)),
            W(2, em(3, 27, 3 << 10))]
    assert "embedding_cache_thrash" not in rules_fired(idle)
    # Boundary: exactly AT the floor (25%) is not below it.
    at = [W(0, em(25, 75, 1 << 20)), W(1, em(50, 150, 2 << 20)),
          W(2, em(75, 225, 3 << 20))]
    assert "embedding_cache_thrash" not in rules_fired(at)


def test_replication_lag_boundary():
    """Chain replication trailing the publish cursor (ISSUE 18): fires
    when a server's repl_lag_rounds stays above the floor for 2
    consecutive windows; quiet on one bad window, lag at the floor,
    replication unarmed, or a lag that recovered."""
    def srv(l0, l1, armed=True):
        return {"server": {"repl_armed": armed,
                           "servers": {"0": {"repl_lag_rounds": l0},
                                       "1": {"repl_lag_rounds": l1}}}}

    # Fires: server 1's lag > 3 (default floor) for 2 windows.
    hot = [W(0, **srv(0, 5)), W(1, **srv(0, 6))]
    assert "replication_lag" in rules_fired(hot)
    diag = doctor.evaluate_stream(hot)
    f = next(x for x in diag["open"] if x["rule"] == "replication_lag")
    assert f["subject"] == "server=1"
    assert f["evidence"]["lag_history"] == [5, 6]
    assert f["playbook"].endswith("#rule-replication_lag")
    # One hot window is not persistence (threshold = 2 windows).
    assert "replication_lag" not in rules_fired([W(0, **srv(0, 9))])
    # Exactly AT the floor (3) is not above it.
    at = [W(0, **srv(0, 3)), W(1, **srv(0, 3))]
    assert "replication_lag" not in rules_fired(at)
    # Recovered in the second window: quiet (every window must exceed).
    rec = [W(0, **srv(0, 9)), W(1, **srv(0, 0))]
    assert "replication_lag" not in rules_fired(rec)
    # Replication unarmed: the rows mean nothing, never fire.
    off = [W(0, **srv(0, 9, armed=False)), W(1, **srv(0, 9, armed=False))]
    assert "replication_lag" not in rules_fired(off)
    # Threshold override: floor 1 catches the lag the default tolerates.
    low = [W(0, **srv(0, 2)), W(1, **srv(0, 2))]
    assert "replication_lag" not in rules_fired(low)
    assert "replication_lag" in rules_fired(low, repl_lag_rounds=1)


def _dev(mfu=None, fallback=False, reason="", platform="cpu",
         intended="", **extra):
    """One window's device section (devprof.window_roll shape)."""
    probe = {"platform": platform, "intended": intended,
             "fallback": fallback, "reason": reason}
    d = {"schema": "bps-device-v1", "probe": probe, "platform": platform,
         "steps": 10, "compute_s": 1.0, "device_step_ms": 100.0,
         "mfu": mfu}
    d.update(extra)
    return {"device": d}


def _wire_keys(wire_s):
    """Window keys whose summed queue + push_wire seconds == wire_s."""
    return {"k": {"components": {"queue": wire_s / 2,
                                 "push_wire": wire_s / 2}}}


def test_device_fallback_boundary():
    """The sentinel's conviction (ISSUE 20): a convicting probe fires
    from the FIRST window (gauge-snapshot law — the silent-CPU class
    must not wait for persistence); a healthy probe, an intended
    platform that matches, or no device section at all stay quiet."""
    hot = [W(0, **_dev(fallback=True, platform="cpu", intended="tpu",
                       reason="intended platform 'tpu' but the jax "
                              "backend initialized as 'cpu'"))]
    assert "device_fallback" in rules_fired(hot)
    diag = doctor.evaluate_stream(hot)
    f = next(x for x in diag["open"] if x["rule"] == "device_fallback")
    assert f["severity"] == "critical"
    assert f["subject"] == "device"
    assert f["evidence"]["platform"] == "cpu"
    assert f["evidence"]["intended"] == "tpu"
    assert f["playbook"].endswith("#rule-device_fallback")
    # Healthy probe: quiet.
    assert "device_fallback" not in rules_fired(
        [W(0, **_dev(platform="cpu", intended="cpu"))])
    # Bare CPU with NO declared intent (the tier-1 suite itself): quiet.
    assert "device_fallback" not in rules_fired(
        [W(0, **_dev(platform="cpu"))])
    # No device section (devprof unarmed / pre-devprof bundle): quiet.
    assert "device_fallback" not in rules_fired([W(0)])
    # A probe error convicts with its reason in the message, on the
    # probe's own evidence (no second process is ever consulted).
    errored = doctor.evaluate_stream([W(0, **_dev(
        fallback=True, platform="unknown(RuntimeError('dead'))",
        reason="device probe errored"))])
    f = next(x for x in errored["open"] if x["rule"] == "device_fallback")
    assert "device probe errored" in f["summary"]
    assert set(f["evidence"]) == {"platform", "intended", "reason"}


def test_mfu_regression_boundary():
    """MFU drop > 25% with the wire flat fires; a drop at the boundary,
    a drop with the wire growing, a missing/None MFU sample on either
    side, and a first-window sample all stay quiet."""
    hot = [W(0, keys=_wire_keys(1.0), **_dev(mfu=0.40)),
           W(1, keys=_wire_keys(1.0), **_dev(mfu=0.20))]
    assert "mfu_regression" in rules_fired(hot)
    diag = doctor.evaluate_stream(hot)
    f = next(x for x in diag["open"] if x["rule"] == "mfu_regression")
    assert f["subject"] == "device"
    assert f["evidence"]["prev_mfu"] == 0.40
    assert f["evidence"]["mfu"] == 0.20
    assert f["playbook"].endswith("#rule-mfu_regression")
    # Exactly AT the threshold (25% drop) is not past it.
    at = [W(0, keys=_wire_keys(1.0), **_dev(mfu=0.40)),
          W(1, keys=_wire_keys(1.0), **_dev(mfu=0.30))]
    assert "mfu_regression" not in rules_fired(at)
    # Same drop but the wire grew >25% too: the wire rules own it.
    congested = [W(0, keys=_wire_keys(1.0), **_dev(mfu=0.40)),
                 W(1, keys=_wire_keys(2.0), **_dev(mfu=0.20))]
    assert "mfu_regression" not in rules_fired(congested)
    # cost_analysis unavailable (mfu None) on either side: quiet.
    assert "mfu_regression" not in rules_fired(
        [W(0, **_dev(mfu=None)), W(1, **_dev(mfu=0.20))])
    assert "mfu_regression" not in rules_fired(
        [W(0, **_dev(mfu=0.40)), W(1, **_dev(mfu=None))])
    # One window has no prev: quiet.
    assert "mfu_regression" not in rules_fired(
        [W(0, **_dev(mfu=0.10))])
    # Threshold override: a 30% drop clears a lowered frac.
    assert "mfu_regression" in rules_fired(at, mfu_regress_frac=0.20)


def test_every_rule_has_a_boundary_test():
    """The fire/no-fire coverage above must track the rule set: a new
    rule without a test here is exactly the drift this file pins."""
    covered = {"persistent_straggler", "round_lag_growth",
               "lane_credit_imbalance", "recv_pool_miss_rate",
               "fusion_dilution", "server_hot_shard",
               "nonfinite_gradients", "audit_mismatch", "barrier_stall",
               "tuner_thrash", "knob_thrash", "param_version_stall",
               "embedding_cache_thrash", "replication_lag",
               "device_fallback", "mfu_regression"}
    # The cross-worker fleet rules' fire/no-fire boundaries live in
    # tests/test_fleet.py (they run over ALIGNED fleet windows, not the
    # local summary stream this file drives).
    fleet_covered = {"fleet_straggler_confirmed", "clock_skew",
                     "codec_epoch_divergence", "signal_disagreement"}
    assert set(doctor.RULE_IDS) == covered | fleet_covered


# ---------------------------------------------------------------------------
# Engine behavior: identity, open/close, side effects
# ---------------------------------------------------------------------------
def test_finding_opens_once_refreshes_then_closes():
    tm.reset_registry()
    flightrec.reset(64)
    eng = doctor.DoctorEngine()
    eng.observe(W(0, lag(0, 3)))
    assert eng.diagnosis()["open"] == []          # one window: quiet
    eng.observe(W(1, lag(0, 3)))
    d = eng.diagnosis()
    assert len(d["open"]) == 1 and not d["healthy"]
    eng.observe(W(2, lag(0, 4)))                  # persists: same finding
    d = eng.diagnosis()
    assert len(d["open"]) == 1
    assert d["open"][0]["first_window"] == 1      # identity preserved
    assert d["open"][0]["window"] == 2            # evidence refreshed
    assert d["findings_total"] == 1               # opened ONCE
    ctr = tm.get_registry().counter(
        "bps_doctor_findings_total",
        labels={"rule": "persistent_straggler"})
    assert ctr.value() == 1
    kinds = [e["kind"] for e in flightrec.get_recorder().events()]
    assert kinds.count("doctor_finding") == 1
    eng.observe(W(3, lag(0, 0)))                  # recovered: closes
    d = eng.diagnosis()
    assert d["healthy"] and d["open"] == []
    assert d["findings_total"] == 1               # history remembers


def test_verdict_line():
    eng = doctor.DoctorEngine(emit=False)
    assert "healthy" in eng.verdict_line()
    eng.observe(W(0, lag(0, 2)))
    eng.observe(W(1, lag(0, 2)))
    line = eng.verdict_line()
    assert "1 open finding" in line
    assert "persistent_straggler(worker=1)" in line
    assert "troubleshooting.md" in line


def test_severity_ranking_in_diagnosis():
    eng = doctor.DoctorEngine(emit=False)
    for i in range(2):
        eng.observe(W(i, {**lag(0, 2),
                          "bps_audit_mismatch_total": i}))
    d = eng.diagnosis()
    assert [f["severity"] for f in d["open"]] == ["critical", "warn"]


# ---------------------------------------------------------------------------
# Offline parity: live engine vs tools/bps_doctor.py over the same JSONL
# ---------------------------------------------------------------------------
def _jsonl_lines():
    """A recorded run: pool-miss storm in window 1, a straggler from
    window 2 on, nothing else."""
    lines = []
    for i in range(4):
        metrics = {"bps_transport_pool_hits": 10,
                   "bps_transport_pool_misses": 500 if i >= 1 else 0,
                   'bps_worker_round_lag{worker="0"}': 0,
                   'bps_worker_round_lag{worker="1"}':
                       3 if i >= 2 else 0}
        lines.append({"ts": 1000.0 + 10.0 * i, "metrics": metrics})
    return lines


def test_offline_jsonl_parity(tmp_path):
    lines = _jsonl_lines()
    # LIVE: an engine observing each window as it closes.
    eng = doctor.DoctorEngine(emit=False)
    for s in doctor.summaries_from_metrics_jsonl(lines):
        eng.observe(s)
    live = {(f["rule"], f["subject"])
            for f in eng.diagnosis()["history"]}
    assert ("persistent_straggler", "worker=1") in live
    assert ("recv_pool_miss_rate", "recv_pool") in live
    # OFFLINE: the CLI over the same lines written to disk.
    p = tmp_path / "metrics.jsonl"
    p.write_text("".join(json.dumps(l) + "\n" for l in lines))
    proc = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "bps_doctor.py"),
         str(p), "--json"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    doc = json.loads(proc.stdout)
    (src,) = doc["sources"]
    offline = {(f["rule"], f["subject"])
               for f in src["diagnosis"]["history"]}
    assert offline == live                      # the parity claim
    assert src["diagnosis"]["windows_evaluated"] == 4


def test_offline_fail_on_findings_gate(tmp_path):
    p = tmp_path / "metrics.jsonl"
    p.write_text("".join(json.dumps(l) + "\n"
                         for l in _jsonl_lines()))
    proc = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "bps_doctor.py"),
         str(p), "--json", "--fail-on-findings"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 3
    clean = tmp_path / "clean.jsonl"
    clean.write_text(json.dumps({"ts": 1.0, "metrics": {}}) + "\n")
    proc = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "bps_doctor.py"),
         str(clean), "--json", "--fail-on-findings"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0


# ---------------------------------------------------------------------------
# Postmortem bundle: diagnosis section + offline replay + rendering
# ---------------------------------------------------------------------------
def _fake_plane_history():
    return [W(0, lag(0, 2)), W(1, lag(0, 2))]


def test_bundle_carries_diagnosis_and_replays(tmp_path):
    flightrec.reset(128)
    eng = doctor.DoctorEngine(emit=True)
    for s in _fake_plane_history():
        eng.observe(s)
    flightrec.set_extra_provider(
        lambda: {"diagnosis": eng.diagnosis(),
                 "signals": _fake_plane_history()},
        name="doctor")
    try:
        path = flightrec.dump_bundle("test", directory=str(tmp_path))
    finally:
        flightrec.set_extra_provider(None, name="doctor")
    assert path
    doc = json.load(open(path))
    diag = doc["extra"]["diagnosis"]
    assert diag["open"][0]["rule"] == "persistent_straggler"
    assert doc["extra"]["signals"][0]["schema"] == "bps-signal-window-v1"
    # Offline replay over the bundle reproduces the finding.
    proc = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "bps_doctor.py"),
         str(tmp_path), "--json"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout)
    (src,) = out["sources"]
    assert any(f["rule"] == "persistent_straggler"
               for f in src["diagnosis"]["open"])
    assert any(f["rule"] == "persistent_straggler"
               for f in src["recorded_open"])
    # tools/postmortem.py shows the findings next to the timeline.
    import postmortem
    bundles = postmortem.load_bundles([str(tmp_path)])
    analysis = postmortem.analyze(bundles)
    assert analysis["diagnosis"][0]["rule"] == "persistent_straggler"
    text = postmortem.render(analysis)
    assert "doctor findings open at dump time" in text
    assert "persistent_straggler" in text
    # The doctor_finding flight event rides the merged timeline too.
    assert any(e.get("kind") == "doctor_finding"
               for e in analysis["events"])


def test_bps_top_renders_doctor_panel():
    import bps_top
    diag = {"armed": True, "window": 7, "open": [
        {"rule": "persistent_straggler", "severity": "warn",
         "subject": "worker=1", "summary": "worker 1 trails",
         "playbook": "docs/troubleshooting.md#rule-persistent_straggler"}],
        "findings_total": 1}
    lines = bps_top.render({}, {}, 1.0, diagnosis=diag)
    joined = "\n".join(lines)
    assert "doctor: 1 open finding(s)" in joined
    assert "persistent_straggler (worker=1)" in joined
    assert "#rule-persistent_straggler" in joined
    healthy = "\n".join(bps_top.render(
        {}, {}, 1.0, diagnosis={"armed": True, "window": 3, "open": [],
                                "findings_total": 0}))
    assert "doctor: healthy" in healthy
    # Plane off (no /diagnosis route): no panel at all.
    off = "\n".join(bps_top.render({}, {}, 1.0, diagnosis=None))
    assert "doctor" not in off
