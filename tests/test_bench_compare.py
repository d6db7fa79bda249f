"""Bench-trajectory regression gate self-test (tools/bench_compare.py,
ISSUE 12 satellite): the gate that keeps future PRs from silently
regressing an on-chip baseline must itself be pinned — synthetic record
series exercise the flag/no-flag boundary, fallback-baseline exclusion,
direction inference, and the CLI contract.
"""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(ROOT, "tools")
if TOOLS not in sys.path:
    sys.path.insert(0, TOOLS)

import bench_compare  # noqa: E402


def R(seq, metric, value, unit="tokens_per_sec", platform="tpu",
      fallback=False):
    return {"file": f"BENCH_r{seq:02d}.json", "seq": seq,
            "metric": metric, "value": value, "unit": unit,
            "platform": platform, "fallback": fallback}


def test_flags_regression_over_threshold():
    recs = [R(1, "throughput", 100.0), R(2, "throughput", 110.0),
            R(3, "throughput", 95.0)]       # -13.6% vs best prior (110)
    rep = bench_compare.check(recs, threshold=0.10)
    assert len(rep["regressions"]) == 1
    row = rep["regressions"][0]
    assert row["baseline"] == 110.0 and row["latest"] == 95.0
    assert row["status"] == "REGRESSED"
    # Within threshold: ok.
    recs[-1] = R(3, "throughput", 100.0)    # -9.1%
    assert bench_compare.check(recs, threshold=0.10)["regressions"] == []


def test_fallback_records_never_baseline():
    """A fallback record must not become the bar the
    next honest record is judged against — and fallback candidates only
    compare within their own platform group."""
    recs = [R(1, "throughput", 100.0),
            R(2, "throughput", 500.0, fallback=True),  # bogus number
            R(3, "throughput", 99.0)]
    rep = bench_compare.check(recs, threshold=0.10)
    assert rep["regressions"] == []          # judged vs 100, not 500
    (row,) = [r for r in rep["groups"] if r["metric"] == "throughput"]
    assert row["baseline"] == 100.0
    # A series with ONLY fallback priors has no baseline at all.
    rep = bench_compare.check(
        [R(1, "m", 100.0, fallback=True), R(2, "m", 1.0)])
    assert rep["groups"][0]["status"] == "no-baseline"
    assert rep["regressions"] == []


def test_lower_is_better_direction():
    recs = [R(1, "fault_recovery_ms", 80.0, unit="ms"),
            R(2, "fault_recovery_ms", 100.0, unit="ms")]  # +25% worse
    rep = bench_compare.check(recs, threshold=0.10)
    assert len(rep["regressions"]) == 1
    # Getting faster is never a regression.
    recs[-1] = R(2, "fault_recovery_ms", 40.0, unit="ms")
    assert bench_compare.check(recs)["regressions"] == []


def test_autotune_family_direction():
    """BENCH_AUTOTUNE records (ISSUE 13): the headline is the step-time
    GAP vs the hand-tuned config — lower is better, even though the
    "pct" unit would otherwise read as higher-is-better."""
    assert bench_compare._lower_is_better(
        "autotune_step_time_gap_pct", "pct_gap")
    recs = [R(1, "autotune_step_time_gap_pct", 3.0, unit="pct_gap"),
            R(2, "autotune_step_time_gap_pct", 20.0, unit="pct_gap")]
    rep = bench_compare.check(recs, threshold=0.10)
    assert len(rep["regressions"]) == 1      # the gap WIDENED: regression
    # The tuner converging (gap shrinking, even negative) is never a
    # regression.
    recs[-1] = R(2, "autotune_step_time_gap_pct", -5.0, unit="pct_gap")
    assert bench_compare.check(recs)["regressions"] == []


def test_serveropt_family_direction():
    """BENCH_SERVEROPT records (ISSUE 14): the headline is the step-time
    gap between the server-resident update stage and the worker-local
    optax baseline — same gap family as BENCH_AUTOTUNE, lower is
    better (negative = the server mode is outright faster)."""
    assert bench_compare._lower_is_better(
        "serveropt_step_time_gap_pct", "pct_gap")
    recs = [R(1, "serveropt_step_time_gap_pct", -20.0, unit="pct_gap"),
            R(2, "serveropt_step_time_gap_pct", 15.0, unit="pct_gap")]
    rep = bench_compare.check(recs, threshold=0.10)
    assert len(rep["regressions"]) == 1      # server mode got slower
    recs[-1] = R(2, "serveropt_step_time_gap_pct", -30.0, unit="pct_gap")
    assert bench_compare.check(recs)["regressions"] == []


def test_knob_family_direction():
    """BENCH_KNOB records (ISSUE 16): the headline is the step-time gap
    between a cold-start job whose predictive tuner discovers the
    global knobs live (actuated CMD_KNOB sets + cost-model codec
    jumps) and the hand-tuned expert config — same gap family, lower
    is better (<= 0 = the knob plane matched/beat the expert)."""
    assert bench_compare._lower_is_better(
        "knob_step_time_gap_pct", "pct_gap")
    recs = [R(1, "knob_step_time_gap_pct", -2.0, unit="pct_gap"),
            R(2, "knob_step_time_gap_pct", 12.0, unit="pct_gap")]
    rep = bench_compare.check(recs, threshold=0.10)
    assert len(rep["regressions"]) == 1      # cold start stopped converging
    recs[-1] = R(2, "knob_step_time_gap_pct", -6.0, unit="pct_gap")
    assert bench_compare.check(recs)["regressions"] == []


def test_sparse_family_direction():
    """BENCH_SPARSE records (ISSUE 17): rows/s served and cache hit
    rate are HIGHER-is-better (including the "rows_per_s" unit, which
    ends in "_s" and would otherwise read as a latency), and the
    percentile-tail family (p50_/p90_/p95_/p99_ prefixes) is
    lower-is-better whatever the name's suffix spells."""
    for metric, unit in [
        ("sparse_lookup_rows_per_s", "rows_per_s"),
        ("embed_cache_hit_rate", "ratio"),
        ("serving_hit_rate", ""),               # suffix alone decides
    ]:
        assert not bench_compare._lower_is_better(metric, unit), \
            (metric, unit)
    for metric, unit in [
        ("p99_pull_ms", "ms"),
        ("p99_pull", ""),                       # prefix alone decides
        ("p95_lookup_tail", ""),
        ("p50_round_ms", "cpu_fallback_ms"),
    ]:
        assert bench_compare._lower_is_better(metric, unit), (metric, unit)

    # End to end: rows/s falling 1M -> 0.5M is the regression (not a
    # "latency improvement")...
    recs = [R(1, "sparse_lookup_rows_per_s", 1e6, unit="rows_per_s"),
            R(2, "sparse_lookup_rows_per_s", 5e5, unit="rows_per_s")]
    rep = bench_compare.check(recs, threshold=0.10)
    assert len(rep["regressions"]) == 1
    assert rep["groups"][0]["direction"] == "higher"
    # ...and a p99 tail growing 25% flags even with a bare name.
    recs = [R(1, "p99_pull", 2.0, unit=""),
            R(2, "p99_pull", 2.5, unit="")]
    rep = bench_compare.check(recs, threshold=0.10)
    assert len(rep["regressions"]) == 1
    assert rep["groups"][0]["direction"] == "lower"


def test_robustness_family_direction():
    """BENCH_ELASTIC replication records (ISSUE 18): lost rounds on a
    failover, replication lag, replication overhead, and the
    autoscaler's detect latency are all LOWER-is-better — 0 is the law
    for the first three — while a bare "_rounds" progress counter keeps
    reading higher-is-better (the rule names the loss/lag shapes
    explicitly, it does not blanket the suffix)."""
    for metric, unit in [
        ("failover_lost_rounds", "rounds"),
        ("repl_lag_rounds", "rounds"),
        ("repl_overhead_pct", "pct"),
        ("autoscale_detect_ms", "ms"),          # via the _ms time rule
    ]:
        assert bench_compare._lower_is_better(metric, unit), (metric, unit)
    # A progress counter is NOT a loss metric: more rounds completed is
    # better, and the robustness rule must not flip it.
    assert not bench_compare._lower_is_better("completed_rounds", "rounds")

    # End to end: a failover that starts losing rounds (0 -> 1) flags
    # against the zero baseline...
    recs = [R(1, "failover_lost_rounds", 0.0, unit="rounds"),
            R(2, "failover_lost_rounds", 1.0, unit="rounds")]
    rep = bench_compare.check(recs, threshold=0.10)
    assert len(rep["regressions"]) == 1
    assert rep["groups"][0]["direction"] == "lower"
    # ...staying at zero is ok...
    recs[-1] = R(2, "failover_lost_rounds", 0.0, unit="rounds")
    assert bench_compare.check(recs, threshold=0.10)["regressions"] == []
    # ...and replication getting CHEAPER must not read as a regression.
    recs = [R(1, "repl_overhead_pct", 40.0, unit="pct"),
            R(2, "repl_overhead_pct", 12.0, unit="pct")]
    assert bench_compare.check(recs, threshold=0.10)["regressions"] == []


def test_fleet_family_direction():
    """BENCH_FLEET headlines (ISSUE 19): the goodput ledger's compute
    share is HIGHER-is-better (named explicitly — a *_pct fallthrough
    must never flip it), the armed plane's round overhead reads lower
    via the _ms time rule."""
    assert not bench_compare._lower_is_better("fleet_goodput_pct", "pct")
    assert bench_compare._lower_is_better("fleet_plane_overhead_ms", "ms")

    # End to end: goodput IMPROVING (60 -> 80) must not flag...
    recs = [R(1, "fleet_goodput_pct", 60.0, unit="pct"),
            R(2, "fleet_goodput_pct", 80.0, unit="pct")]
    rep = bench_compare.check(recs, threshold=0.10)
    assert rep["regressions"] == []
    assert rep["groups"][0]["direction"] == "higher"
    # ...goodput COLLAPSING flags...
    recs[-1] = R(2, "fleet_goodput_pct", 30.0, unit="pct")
    assert len(bench_compare.check(recs, threshold=0.10)["regressions"]) == 1
    # ...and the plane's overhead growing flags as a regression.
    recs = [R(1, "fleet_plane_overhead_ms", 0.1, unit="ms"),
            R(2, "fleet_plane_overhead_ms", 5.0, unit="ms")]
    assert len(bench_compare.check(recs, threshold=0.10)["regressions"]) == 1


def test_device_family_direction():
    """The devprof headlines (ISSUE 20): MFU and percent-of-peak shapes
    are HIGHER-is-better by metric suffix AND by unit alone, and a
    collapsing MFU flags as the regression — not an improving one."""
    assert not bench_compare._lower_is_better("flagship_mfu", "mfu")
    assert not bench_compare._lower_is_better(
        "matmul_pct_of_peak", "pct_of_peak")
    # Unit alone decides when the metric name carries no suffix hint.
    assert not bench_compare._lower_is_better("headline", "pct_of_peak")
    # The device-step time itself stays lower-is-better.
    assert bench_compare._lower_is_better("device_step_ms", "ms")

    # End to end: MFU falling 0.4 -> 0.2 flags...
    recs = [R(1, "flagship_mfu", 0.4, unit="mfu"),
            R(2, "flagship_mfu", 0.2, unit="mfu")]
    rep = bench_compare.check(recs, threshold=0.10)
    assert len(rep["regressions"]) == 1
    assert rep["groups"][0]["direction"] == "higher"
    # ...and pct-of-peak RISING never does.
    recs = [R(1, "matmul_pct_of_peak", 40.0, unit="pct_of_peak"),
            R(2, "matmul_pct_of_peak", 55.0, unit="pct_of_peak")]
    assert bench_compare.check(recs, threshold=0.10)["regressions"] == []


def test_throughput_units_are_higher_is_better():
    """The unit-direction law (ISSUE 15 satellite): *_mbps / *_goodput /
    throughput-ish units are explicitly HIGHER-is-better — including
    rate names ending in "_s" that the time-suffix rule would otherwise
    misread as latencies — and a throughput DROP flags as the
    regression, not a rise."""
    for metric, unit in [
        ("wire_goodput_mbps", "mbps"),
        ("transport_goodput", "pct_of_floor"),
        ("embedding_rows_per_s", "per_s"),      # "_s" suffix trap
        ("pull_qps", "qps"),
        ("bert_large_mfu", "mfu"),
        ("dp_scaling_efficiency", "ratio"),
        ("hier_wire_bytes_saved_pct", "pct"),
        ("some_metric", "MB/s"),                # unit alone decides
    ]:
        assert not bench_compare._lower_is_better(metric, unit), \
            (metric, unit)
    # ...and the time family still reads lower-is-better, including
    # under the cpu_fallback_ unit prefix.
    for metric, unit in [
        ("fault_recovery_ms", "ms"),
        ("bert_step_time_s", "s"),
        ("join_catchup_ms", "cpu_fallback_ms"),
        ("autotune_step_time_gap_pct", "pct_gap"),
    ]:
        assert bench_compare._lower_is_better(metric, unit), (metric, unit)

    # End to end: goodput falling 9 -> 5 mbps is the regression...
    recs = [R(1, "wire_goodput_mbps", 9.0, unit="mbps"),
            R(2, "wire_goodput_mbps", 5.0, unit="mbps")]
    rep = bench_compare.check(recs, threshold=0.10)
    assert len(rep["regressions"]) == 1
    assert rep["groups"][0]["direction"] == "higher"
    # ...and rising throughput never is.
    recs[-1] = R(2, "wire_goodput_mbps", 20.0, unit="mbps")
    assert bench_compare.check(recs)["regressions"] == []
    # The "_s" trap, end to end: rows/s DOUBLING must not flag.
    recs = [R(1, "embedding_rows_per_s", 1000.0, unit="per_s"),
            R(2, "embedding_rows_per_s", 2000.0, unit="per_s")]
    assert bench_compare.check(recs)["regressions"] == []


def test_platforms_compared_separately():
    recs = [R(1, "eff", 1.0, platform="tpu"),
            R(2, "eff", 0.2, platform="cpu"),   # different hardware
            R(3, "eff", 0.98, platform="tpu")]
    rep = bench_compare.check(recs, threshold=0.10)
    assert rep["regressions"] == []
    assert len(rep["groups"]) == 2


def test_load_records_shapes(tmp_path):
    """Loader handles the bench.py wrapper shape, the raw shape, the
    MULTICHIP ok-record shape, and skips garbage."""
    (tmp_path / "BENCH_r01.json").write_text(json.dumps({
        "n": 1, "rc": 0,
        "parsed": {"metric": "m1", "value": 10.0, "unit": "x",
                   "detail": {"device_platform": "tpu"}}}))
    (tmp_path / "BENCH_r02.json").write_text(json.dumps({
        "metric": "m1", "value": 12.0, "unit": "x",
        "detail": {"device_platform": "tpu"}}))
    (tmp_path / "BENCH_r03.json").write_text(json.dumps({
        "n": 3, "rc": 0,
        "parsed": {"metric": "m1", "value": 11.0,
                   "unit": "cpu_fallback_x",
                   "detail": {"note": "cpu-fallback: no accelerator",
                              "fallback": True}}}))
    (tmp_path / "MULTICHIP_r01.json").write_text(json.dumps(
        {"n_devices": 8, "rc": 0, "ok": True}))
    (tmp_path / "MULTICHIP_r02.json").write_text(json.dumps(
        {"n_devices": 8, "rc": 1, "ok": False}))
    (tmp_path / "BENCH_r04.json").write_text("{not json")
    recs = bench_compare.load_records(str(tmp_path))
    by = {(r["metric"], r["seq"]): r for r in recs}
    assert by[("m1", 1)]["platform"] == "tpu"
    assert by[("m1", 2)]["value"] == 12.0
    assert by[("m1", 3)]["fallback"] is True
    assert by[("m1", 3)]["platform"] == "cpu"
    assert by[("multichip_dryrun_ok", 2)]["value"] == 0.0
    # The broken multichip run IS a 100% regression of its ok bit.
    rep = bench_compare.check(recs)
    assert any(r["metric"] == "multichip_dryrun_ok"
               for r in rep["regressions"])


def test_cli_over_a_record_series(tmp_path):
    """The CLI over a directory of records emits valid JSON and the text
    report; a fallback record is excluded as a baseline.  Over the repo
    root itself — whose old record series was deleted with the plug-in
    it was made behind — it finds nothing to compare and exits 0."""
    for n, (value, detail) in enumerate([
            (1.00, {"device_platform": "tpu"}),
            (9.99, {"device_platform": "cpu", "fallback": True}),
            (0.99, {"device_platform": "tpu"})], start=1):
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps({
            "n": n, "rc": 0, "parsed": {"metric": "eff", "value": value,
                                        "unit": "x", "detail": detail}}))
    cli = [sys.executable, os.path.join(TOOLS, "bench_compare.py")]
    proc = subprocess.run([*cli, str(tmp_path), "--json"],
                          capture_output=True, text=True, timeout=120)
    doc = json.loads(proc.stdout)
    assert proc.returncode == 0 and doc["regressions"] == []
    tpu = next(g for g in doc["groups"] if g["platform"] == "tpu")
    assert tpu["baseline"] == 1.00 and tpu["status"] == "ok"
    text = subprocess.run([*cli, str(tmp_path)],
                          capture_output=True, text=True, timeout=120)
    assert "bench_compare:" in text.stdout
    empty = subprocess.run([*cli, ROOT, "--json"],
                           capture_output=True, text=True, timeout=120)
    assert empty.returncode == 0
    assert json.loads(empty.stdout)["groups"] == []
