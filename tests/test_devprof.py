"""Device-plane profiler tests (common/devprof.py, ISSUE 20): peak-FLOPs
resolution, the cost_analysis cache, MFU window math, the sentinel
conviction law, trace-lane schema + XLA merge, the signals/doctor/
flightrec integrations, and the off-is-really-off wire contract.
"""

import json
import os
import struct
import sys

import numpy as np
import pytest

from byteps_tpu.common import devprof
from byteps_tpu.common import doctor as doctor_mod
from byteps_tpu.common import goodput, signals, trace_analysis
from byteps_tpu.common import telemetry as tm
from byteps_tpu.common.devprof import DeviceProfiler
from byteps_tpu.server.client import (PSSession, CMD_HELLO, CMD_INIT,
                                      CMD_PUSH, CMD_PULL)

from testutil import StubPSServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(ROOT, "tools")
if TOOLS not in sys.path:
    sys.path.insert(0, TOOLS)


@pytest.fixture(autouse=True)
def _clean_plane(monkeypatch):
    """No process-wide profiler and no peak-FLOPs override leak
    between tests (the tier-1 environment must not change verdicts)."""
    devprof.disarm()
    monkeypatch.delenv("BYTEPS_TPU_PEAK_FLOPS", raising=False)
    yield
    devprof.disarm()


def _init_cpu_backend():
    """Ensure the jax CPU backend is initialized (the sentinel's
    'a backend actually came up' precondition)."""
    import jax
    jax.devices()


def _summary(sec, window=0, ts=1.0):
    """A minimal signal-window summary carrying one device section."""
    return {"schema": "bps-signal-window-v1", "window": window, "ts": ts,
            "dur_s": 1.0, "keys": {}, "metrics": {}, "events": {},
            "device": sec}


# ---------------------------------------------------------------------------
# Peak-FLOPs resolution
# ---------------------------------------------------------------------------
def test_peak_flops_table_prefix_match():
    assert devprof.peak_flops(kind="TPU v4 megacore") == 275e12
    assert devprof.peak_flops(kind="TPU v5 lite podslice") == 197e12
    assert devprof.peak_flops(kind="TPU v5p slice") == 459e12
    # Unknown kinds (CPU hosts) are 0.0 — MFU then reports None, never
    # a made-up number.
    assert devprof.peak_flops(kind="cpu") == 0.0
    assert devprof.peak_flops(kind="") == 0.0


def test_peak_flops_env_overrides(monkeypatch):
    monkeypatch.setenv("BYTEPS_TPU_PEAK_FLOPS", "1.5e12")
    assert devprof.peak_flops(kind="cpu") == 1.5e12
    assert devprof.peak_flops(kind="TPU v4") == 1.5e12  # override wins
    monkeypatch.setenv("BYTEPS_TPU_PEAK_FLOPS", "not-a-number")
    assert devprof.peak_flops(kind="TPU v4") == 275e12  # falls to table


# ---------------------------------------------------------------------------
# cost_analysis cache: one lower+compile per jitted callable
# ---------------------------------------------------------------------------
def test_cost_cache_one_analysis_per_callable(monkeypatch):
    calls = []
    monkeypatch.setattr(devprof, "cost_analysis_flops",
                        lambda fn, args: calls.append(fn) or 123.0)

    def f1():
        pass

    def f2():
        pass

    prof = DeviceProfiler(telemetry_on=False)
    assert prof.flops_for(f1, ()) == 123.0
    assert prof.flops_for(f1, ()) == 123.0
    assert prof.flops_for(f2, ()) == 123.0
    assert len(calls) == 2                  # one analysis per callable
    assert prof.cost_cache_hits == 1
    assert prof.cost_cache_misses == 2
    assert prof.profile()["cost_cache"] == {"hits": 1, "misses": 2,
                                            "entries": 2}


def test_cost_analysis_graceful_on_non_jitted():
    # A callable with no .lower() must downgrade to None, never raise.
    assert devprof.cost_analysis_flops(lambda x: x, (1,)) is None


def test_cost_analysis_real_jit():
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((32, 32), jnp.float32)
    flops = devprof.cost_analysis_flops(f, (x,))
    # CPU backends usually report; if this one doesn't, None is the
    # contract (time-only reporting), not a failure.
    assert flops is None or flops > 0


# ---------------------------------------------------------------------------
# Window math: device_step_ms and MFU
# ---------------------------------------------------------------------------
def test_window_roll_mfu_math(monkeypatch):
    monkeypatch.setenv("BYTEPS_TPU_PEAK_FLOPS", "1e12")
    prof = DeviceProfiler(telemetry_on=False)
    # One 100 ms step at 5e10 FLOPs -> 5e11 FLOPs/s -> MFU 0.5.
    prof.note_step(0, 100_000_000, flops=5e10)
    sec = prof.window_roll()
    assert sec["schema"] == devprof.SCHEMA
    assert sec["steps"] == 1
    assert sec["device_step_ms"] == pytest.approx(100.0)
    assert sec["compute_s"] == pytest.approx(0.1)
    assert sec["flops_per_s"] == pytest.approx(5e11)
    assert sec["mfu"] == pytest.approx(0.5)
    assert sec["peak_flops"] == 1e12
    # The roll drained the window: next one is empty.
    sec2 = prof.window_roll()
    assert sec2["steps"] == 0
    assert sec2["device_step_ms"] is None
    assert sec2["mfu"] is None
    # Lifetime totals survive the drain.
    assert prof.steps_total == 1
    assert prof.device_s_total == pytest.approx(0.1)


def test_window_roll_without_flops_downgrades(monkeypatch):
    monkeypatch.setenv("BYTEPS_TPU_PEAK_FLOPS", "1e12")
    prof = DeviceProfiler(telemetry_on=False)
    prof.note_step(0, 100_000_000)          # backend reported no FLOPs
    sec = prof.window_roll()
    assert sec["device_step_ms"] == pytest.approx(100.0)  # time survives
    assert sec["mfu"] is None
    assert sec["flops_per_s"] is None


def test_window_roll_unknown_peak_gives_mfu_none():
    _init_cpu_backend()                     # device_kind "cpu" -> peak 0
    prof = DeviceProfiler(telemetry_on=False)
    prof.note_step(0, 100_000_000, flops=5e10)
    sec = prof.window_roll()
    assert sec["peak_flops"] is None
    assert sec["mfu"] is None               # never a made-up number
    assert sec["flops_per_s"] == pytest.approx(5e11)   # still reported


def test_window_roll_updates_gauges(monkeypatch):
    monkeypatch.setenv("BYTEPS_TPU_PEAK_FLOPS", "1e12")
    _init_cpu_backend()
    tm.reset_registry()
    prof = DeviceProfiler(worker=2)         # telemetry on
    prof.note_step(0, 100_000_000, flops=5e10)
    prof.window_roll()
    snap = tm.get_registry().snapshot()
    assert snap['bps_device_step_ms{worker="2"}'] == pytest.approx(100.0)
    assert snap['bps_mfu{worker="2"}'] == pytest.approx(0.5)
    fb = {k: v for k, v in snap.items()
          if k.startswith("bps_device_fallback")}
    (label, val), = fb.items()
    assert 'worker="2"' in label and 'platform="cpu"' in label
    assert val == 0.0                       # no intent declared: healthy
    tm.reset_registry()


def test_unarmed_registers_zero_gauges():
    """Quiet-when-unarmed: no profiler -> the registry never learns the
    device gauge names (the monitoring.md contract)."""
    tm.reset_registry()
    assert devprof.active() is None
    assert devprof.step_begin(lambda: None, ()) is None
    devprof.step_end(None)
    snap = tm.get_registry().snapshot()
    assert not any(k.startswith(("bps_device", "bps_mfu")) for k in snap)
    tm.reset_registry()


# ---------------------------------------------------------------------------
# The sentinel conviction law
# ---------------------------------------------------------------------------
def test_sentinel_bare_cpu_without_intent_is_healthy():
    _init_cpu_backend()
    prof = DeviceProfiler(intended_platform="")
    probe = prof.probe()
    assert probe["platform"] == "cpu"
    assert probe["fallback"] is False
    assert probe["reason"] == ""


def test_sentinel_intended_platform_mismatch_convicts():
    _init_cpu_backend()
    prof = DeviceProfiler(intended_platform="tpu")
    probe = prof.probe()
    assert probe["fallback"] is True
    assert probe["intended"] == "tpu"
    assert "intended platform 'tpu'" in probe["reason"]
    assert "'cpu'" in probe["reason"]
    # Matching intent stays quiet.
    assert DeviceProfiler(intended_platform="cpu").probe()["fallback"] \
        is False


def test_sentinel_host_only_with_intent_stays_quiet(monkeypatch):
    monkeypatch.setattr(devprof, "device_stamp",
                        lambda: {"device_platform": "none(host-only)"})
    probe = DeviceProfiler(intended_platform="tpu").probe()
    assert probe["fallback"] is False       # nothing to convict yet


def test_sentinel_probe_error_convicts_without_a_child(monkeypatch):
    """A probe error convicts on the stamp alone.  The sentinel starts no
    child process: a chip belongs to one process, so a child probing the
    default backend while this one holds the chip could only fail or
    hang."""
    import subprocess
    monkeypatch.setattr(devprof, "device_stamp",
                        lambda: {"device_platform": "unknown(boom)"})

    def no_child(*a, **kw):
        raise AssertionError("the sentinel must not start a process")

    monkeypatch.setattr(subprocess, "Popen", no_child)
    monkeypatch.setattr(subprocess, "run", no_child)
    prof = DeviceProfiler(intended_platform="tpu")
    for _ in range(2):
        probe = prof.probe()
        assert probe["fallback"] is True
        assert probe["reason"].startswith("device probe failed")
        assert set(probe) == {"platform", "intended", "fallback",
                              "reason"}


# ---------------------------------------------------------------------------
# Trace lanes: schema, pid bands, XLA merge, capture parsing
# ---------------------------------------------------------------------------
def test_trace_events_land_on_device_lane():
    prof = DeviceProfiler(telemetry_on=False)
    prof.note_step(5_000_000, 7_000_000)    # 5000 µs .. +2000 µs
    (ev,), = (prof.trace_events(rank=3),)
    assert ev["pid"] == trace_analysis.DEVICE_PID_BASE + 3
    assert ev["tid"] == "DEVICE"
    assert ev["ph"] == "X" and ev["cat"] == "device"
    assert ev["ts"] == 5000 and ev["dur"] == 2000
    assert ev["args"]["step"] == 1
    # The device band is NOT the server band: the critical-path
    # decomposition must keep ignoring device lanes.
    assert not trace_analysis._is_server(ev)
    assert trace_analysis._is_server(
        {"pid": trace_analysis.SERVER_PID_BASE})


# ---------------------------------------------------------------------------
# Hot-path hooks and the armed end-to-end path
# ---------------------------------------------------------------------------
def test_step_hooks_roundtrip_real_jit():
    import jax
    import jax.numpy as jnp
    devprof.arm(worker=0, telemetry_on=False)
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((16, 16), jnp.float32)
    tok = devprof.step_begin(f, (x,))
    assert tok is not None
    out = f(x)
    devprof.step_end(tok, out)
    prof = devprof.active()
    assert prof.steps_total == 1
    assert prof.device_s_total > 0.0
    p = prof.profile()
    assert p["armed"] is True and p["steps_total"] == 1
    assert len(p["recent_step_ms"]) == 1


def test_signal_plane_carries_device_section():
    prof = DeviceProfiler(telemetry_on=False)
    plane = signals.SignalPlane(window_s=1.0,
                                providers={"device": prof.window_roll})
    prof.note_step(0, 50_000_000)
    s = plane.roll()
    assert s["device"]["schema"] == devprof.SCHEMA
    assert s["device"]["steps"] == 1
    assert "probe" in s["device"]


def test_flight_section_shape():
    prof = DeviceProfiler(intended_platform="tpu", telemetry_on=False)
    prof.note_step(0, 10_000_000)
    prof.window_roll()
    sec = prof.flight_section()["device"]
    assert sec["schema"] == devprof.SCHEMA
    assert sec["steps_total"] == 1
    assert sec["last_window"]["steps"] == 1
    assert sec["probe"]["intended"] == "tpu"
    assert sec["recent_step_ms"] == [10.0]


def test_get_device_profile_api_shapes():
    from byteps_tpu.common import api
    assert api.get_device_profile() == {
        "armed": False, "platform": None, "mfu": None,
        "steps_total": 0, "device_s_total": 0.0, "mean_step_ms": None}
    devprof.arm(worker=1, telemetry_on=False)
    doc = api.get_device_profile()
    assert doc["armed"] is True and doc["worker"] == 1
    assert doc["steps_total"] == 0 and doc["mean_step_ms"] is None


# ---------------------------------------------------------------------------
# Goodput: measured device seconds land IN the compute bucket
# ---------------------------------------------------------------------------
def test_goodput_device_compute_exact_partition():
    doc = {"dur_s": 10.0, "window": 0, "worker": 0,
           "components": {"queue": 1.0, "push_wire": 1.0, "serve": 2.0,
                          "device_compute": 3.0},
           "events": {}}
    led = goodput.worker_ledger(doc)
    assert led["wire"] == pytest.approx(2.0)
    assert led["straggler_wait"] == pytest.approx(2.0)
    assert led["compute"] == pytest.approx(6.0)     # 3 measured + 3 rest
    assert sum(led.values()) == pytest.approx(10.0)
    # device_compute=0 is arithmetically the old ledger.
    doc2 = dict(doc, components={"queue": 1.0, "push_wire": 1.0,
                                 "serve": 2.0})
    assert goodput.worker_ledger(doc2) == pytest.approx(led)
    # Oversubscribed measured components scale down; still exact.
    doc3 = {"dur_s": 2.0, "window": 0, "worker": 0,
            "components": {"push_wire": 2.0, "serve": 2.0,
                           "device_compute": 2.0}, "events": {}}
    assert sum(goodput.worker_ledger(doc3).values()) == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# Doctor e2e: a forced mismatch opens device_fallback within one window,
# live AND from an offline bundle replay (parity by construction)
# ---------------------------------------------------------------------------
def test_fallback_opens_critical_finding_live_and_offline(tmp_path,
                                                          capsys):
    _init_cpu_backend()
    prof = DeviceProfiler(intended_platform="tpu", telemetry_on=False)
    sec = prof.window_roll()
    assert sec["probe"]["fallback"] is True
    summary = _summary(sec)
    # Live: first window is enough (gauge-snapshot rule, no delta).
    eng = doctor_mod.DoctorEngine(emit=False)
    fired = [f for f in eng.observe(summary)
             if f["rule"] == "device_fallback"]
    assert fired and fired[0]["severity"] == doctor_mod.SEV_CRITICAL
    assert fired[0]["subject"] == "device"
    # Offline: the same summary replayed from a postmortem bundle file
    # through the real CLI reaches the same verdict.
    bundle = tmp_path / "bps-postmortem-r0-test-1-1.json"
    bundle.write_text(json.dumps({"schema": "bps-postmortem-v1",
                                  "rank": 0,
                                  "extra": {"signals": [summary]}}))
    import bps_doctor
    rc = bps_doctor.main([str(bundle), "--json"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["mode"] == "offline"
    (src,) = out["sources"]
    rules = {f["rule"] for f in src["diagnosis"]["open"]}
    assert "device_fallback" in rules


def test_bundle_device_section_renders_in_postmortem(tmp_path):
    from byteps_tpu.common import flightrec
    _init_cpu_backend()
    prof = DeviceProfiler(intended_platform="tpu", telemetry_on=False)
    prof.note_step(0, 20_000_000)
    prof.window_roll()
    flightrec.set_extra_provider(prof.flight_section, name="device")
    try:
        path = flightrec.dump_bundle("test", directory=str(tmp_path))
    finally:
        flightrec.set_extra_provider(None, name="device")
    assert path
    import postmortem
    bundles = postmortem.load_bundles([str(tmp_path)])
    analysis = postmortem.analyze(bundles)
    (row,) = analysis["device"]
    assert row["fallback"] is True and row["platform"] == "cpu"
    text = postmortem.render(analysis)
    assert "device plane" in text
    assert "FALLBACK" in text


# ---------------------------------------------------------------------------
# Off is off: arming the device plane never touches the wire
# ---------------------------------------------------------------------------
def _run_stub_roundtrip():
    """One push_pull against a recording stub; returns the raw frames."""
    store = {}

    def handler(cmd, dt, fl, req_id, wid, key, payload):
        if cmd == CMD_HELLO:
            return 0, b"\x00\x00"
        if cmd == CMD_INIT:
            return 0, struct.pack("<Q", 0)
        if cmd == CMD_PUSH:
            store[key] = bytes(payload)
            return 0, b""
        if cmd == CMD_PULL:
            return 0, store[key]
        return 1, b""

    srv = StubPSServer(handler, record=True)
    try:
        s = PSSession(["127.0.0.1"], [srv.port], worker_id=0,
                      num_servers=1, wire_conns=1)
        x = np.arange(256, dtype=np.float32)
        got = s.push_pull(3, x)
        np.testing.assert_array_equal(got, x)
        s.close()
        with srv.lock:
            return list(srv.frames)
    finally:
        srv.close()


def test_devprof_wire_byte_identity():
    """ISSUE-20 acceptance: BYTEPS_TPU_DEVPROF=0 sends zero extra frames
    and the armed plane is strictly local — headers byte-identical
    against a recording stub either way."""
    off_frames = _run_stub_roundtrip()
    prof = devprof.arm(worker=0, telemetry_on=False)
    try:
        on_frames = _run_stub_roundtrip()
        prof.window_roll()                 # rolling is local too
    finally:
        devprof.disarm()
    assert [h for h, _, _ in off_frames] == [h for h, _, _ in on_frames]
