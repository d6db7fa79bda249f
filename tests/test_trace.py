"""Per-partition trace events (reference: global.cc:463-579 closes one span
per partition per pipeline stage; docs/timeline.md documents the schema) —
plus the distributed half: server-side spans over CMD_TRACE, cross-host
clock alignment over timestamped CMD_PING, the merged Perfetto export, the
critical-path analyzer, and the tracing-off byte-identity contract."""

import json
import struct
import time

import numpy as np
import pytest

from byteps_tpu.common import trace_analysis
from byteps_tpu.core.native import get_core
from byteps_tpu.server.client import (PSSession, _REQ, CMD_HELLO,
                                      CMD_INIT, CMD_PUSH, CMD_PULL,
                                      CMD_PING, FLAG_TRACED,
                                      estimate_clock_offset)

from test_ps_server import ps_server  # noqa: F401  (fixture reuse)
from testutil import StubPSServer, cpu_env


@pytest.fixture
def tracing(tmp_path):
    core = get_core()
    core.trace_enable(True)
    yield core
    # flush anything left so later tests start clean
    core.trace_enable(False)
    if core.trace_count():
        core.trace_dump(str(tmp_path / "flush.json"), 0)


def _dump(core, tmp_path):
    path = tmp_path / "comm.json"
    core.trace_dump(str(path), rank=0)
    with open(path) as f:
        return json.load(f)["traceEvents"]


def test_ps_partition_spans(ps_server, tracing, tmp_path):  # noqa: F811
    """A partitioned push_pull emits one QUEUE + PUSH + PULL span per
    partition, carrying key/bytes/priority args."""
    port = ps_server(num_workers=1)
    part_bytes = 4096
    n = 4 * (part_bytes // 4)  # 4 partitions of f32
    sess = PSSession(["127.0.0.1"], [port], worker_id=0, num_servers=1,
                     partition_bytes=part_bytes)
    # A raw key with no registry entry: label falls back to key_<dk>.
    # (Must be outside the declared range — the registry persists across
    # the test session, so a small literal key may own a name by now.)
    dk = get_core().num_declared() + 777
    x = np.arange(n, dtype=np.float32)
    out = sess.push_pull(dk, x, priority=5)
    np.testing.assert_array_equal(out, x)
    sess.close()

    events = _dump(tracing, tmp_path)
    by_stage = {}
    for e in events:
        by_stage.setdefault(e["tid"], []).append(e)
    # one row per partition per stage
    for stage in ("QUEUE", "PUSH", "PULL"):
        rows = by_stage.get(stage, [])
        assert len(rows) == 4, (stage, [e["name"] for e in events])
        for r in rows:
            assert r["ph"] == "X" and r["dur"] >= 0
            assert r["args"]["priority"] == 5
            assert r["args"]["bytes"] > 0
        # 4 distinct partition keys, sharing the declared key
        keys = {r["args"]["key"] for r in rows}
        assert len(keys) == 4
        assert {k >> 16 for k in keys} == {dk}
        assert sorted(r["name"] for r in rows) == [
            f"key_{dk}.part{i}" for i in range(4)]


def test_codec_pipeline_emits_encode_decode_spans(ps_server, tracing,  # noqa: F811
                                                  tmp_path):
    """With a registered compressor, the codec pipeline closes one ENCODE
    span per partition (pool thread, ahead of the dispatcher) and — for
    bidirectional compressors — one DECODE span per partition (pull-leg
    decode off the receiver thread), alongside QUEUE/PUSH/PULL."""
    port = ps_server(num_workers=1)
    sess = PSSession(["127.0.0.1"], [port], worker_id=0, num_servers=1,
                     partition_bytes=1024, min_compress_bytes=0,
                     compress_threads=2)
    dk = get_core().num_declared() + 801
    sess.register_compressor(dk, {"compressor": "onebit"})
    x = np.linspace(-1.0, 1.0, 1024).astype(np.float32)  # 4 partitions
    sess.push_pull(dk, x, priority=3)
    sess.close()

    events = _dump(tracing, tmp_path)
    by_stage = {}
    for e in events:
        by_stage.setdefault(e["tid"], []).append(e)
    for stage in ("QUEUE", "PUSH", "PULL", "ENCODE", "DECODE"):
        rows = by_stage.get(stage, [])
        assert len(rows) == 4, (stage, sorted(by_stage))
        for r in rows:
            assert r["ph"] == "X" and r["dur"] >= 0
            assert r["args"]["priority"] == 3
            assert r["args"]["bytes"] > 0
        assert {k >> 16 for k in (r["args"]["key"] for r in rows)} == {dk}
    # The ENCODE span's bytes are the compressed wire size (onebit:
    # 9-byte header+scale + n/8 sign bits), not the raw partition.
    for r in by_stage["ENCODE"]:
        assert r["args"]["bytes"] == 9 + (1024 // 4) // 8


def test_ps_spans_use_declared_names(ps_server, tracing, tmp_path):  # noqa: F811
    """Sessions driven through the declare() registry label spans with the
    tensor's name, as the reference timeline does."""
    port = ps_server(num_workers=1)
    core = get_core()
    dk = core.declare_tensor("Gradient.traced_tensor")
    sess = PSSession(["127.0.0.1"], [port], worker_id=0, num_servers=1)
    sess.push_pull(dk, np.ones(8, np.float32))
    sess.close()
    events = _dump(tracing, tmp_path)
    names = {e["name"] for e in events if e["tid"] == "PUSH"}
    assert names == {"Gradient.traced_tensor.part0"}


def test_api_step_window_includes_partition_rows(ps_server, tmp_path,  # noqa: F811
                                                 monkeypatch):
    """End-to-end: BYTEPS_TRACE_ON windowing + PS mode dumps a comm.json
    holding both STEP envelopes and per-partition stage rows."""
    import subprocess
    import sys
    import os
    port = ps_server(num_workers=1)
    code = f"""
import numpy as np, jax.numpy as jnp
import byteps_tpu as bps
bps.init()
for step in range(4):
    bps.push_pull(jnp.ones(5000), name="g", average=False)
    bps.mark_step()
bps.shutdown()
"""
    from testutil import cpu_env
    env = cpu_env({
        "BYTEPS_TPU_PS_MODE": "1",
        "DMLC_NUM_WORKER": "1",
        "DMLC_NUM_SERVER": "1",
        "DMLC_PS_ROOT_PORT": str(port - 1),
        "BYTEPS_TRACE_ON": "1",
        "BYTEPS_TRACE_DIR": str(tmp_path),
        "BYTEPS_TRACE_START_STEP": "1",
        "BYTEPS_TRACE_END_STEP": "2",
        "BYTEPS_PARTITION_BYTES": "4096",
        "BYTEPS_LOG_LEVEL": "ERROR",
    })
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, r.stderr
    with open(tmp_path / "0" / "comm.json") as f:
        events = json.load(f)["traceEvents"]
    stages = {e["tid"] for e in events}
    assert "STEP" in stages
    # 5000 f32 at 4096B partitions -> 5 partitions per traced push_pull
    pushes = [e for e in events if e["tid"] == "PUSH"]
    assert len(pushes) >= 5 and all("g.part" in e["name"] for e in pushes)


# ---------------------------------------------------------------------------
# Distributed tracing: server spans, clock alignment, merged export,
# critical path (ISSUE 5)
# ---------------------------------------------------------------------------
def test_clock_offset_math():
    """NTP midpoint: offset = server_ts - (t0+t1)/2 from the MINIMUM-RTT
    sample — noisy high-RTT samples must not pollute the estimate."""
    # Server clock runs 5000us ahead; tight sample rtt=200.
    tight = (1000, 6100, 1200)          # midpoint 1100 -> offset 5000
    # Noisy samples: same true offset but asymmetric delays that would
    # estimate wrong — and larger RTTs, so they must lose.
    noisy = [(2000, 7010, 12000), (3000, 10000, 9000)]
    off, rtt = estimate_clock_offset([noisy[0], tight, noisy[1]])
    assert off == 5000.0
    assert rtt == 200.0
    # Correction maps a server timestamp back onto the worker timeline.
    assert 6100 - off == 1100
    with pytest.raises(ValueError):
        estimate_clock_offset([])


def _recording_server():
    """StubPSServer speaking just enough protocol for one worker's
    push_pull (HELLO mode bytes, INIT completed_round, PUSH stores, PULL
    echoes the stored payload), recording every raw request frame so the
    test can assert on the exact bytes a client emits."""
    store = {}

    def handler(cmd, dt, fl, req_id, wid, key, payload):
        if cmd == CMD_HELLO:
            return 0, b"\x00\x00"
        if cmd == CMD_INIT:
            return 0, struct.pack("<Q", 0)
        if cmd == CMD_PUSH:
            store[key] = payload
            return 0, b""
        if cmd == CMD_PULL:
            return 0, store.get(key, b"")
        return 1, b""

    return StubPSServer(handler, record=True)


def test_wire_byte_identical_when_tracing_off(tmp_path):
    """The tracing-off wire is byte-identical to the pre-trace protocol:
    every header is exactly _REQ.pack with the round in the low 15 bits
    of flags and the marker bit NEVER set (bit 15 belongs exclusively to
    the tracer, so an untraced long run can't bleed a round counter into
    it), and no PING/TRACE frames ride along.  With tracing ON the same
    traffic carries FLAG_TRACED + the round mod 2^15."""
    core = get_core()
    core.trace_enable(False)
    srv = _recording_server()
    sess = None
    try:
        sess = PSSession(["127.0.0.1"], [srv.port], worker_id=0,
                         num_servers=1, partition_bytes=4096, wire_conns=1)
        x = np.arange(2048, dtype=np.float32)        # 8KB -> 2 partitions
        np.testing.assert_array_equal(sess.push_pull(9, x), x)
        with srv.lock:
            frames = list(srv.frames)
        cmds = {f[1] for f in frames}
        assert cmds == {CMD_HELLO, CMD_INIT, CMD_PUSH, CMD_PULL}
        for hdr, cmd, fl in frames:
            c2, d2, f2, r2, w2, k2, l2 = _REQ.unpack(hdr)
            # Byte-identity: re-packing the parsed fields reproduces the
            # frame, and round flags are the raw 16-bit round (round 0
            # here) with no trace bit.
            assert hdr == _REQ.pack(c2, d2, f2, r2, w2, k2, l2)
            assert not (fl & FLAG_TRACED)
            if cmd in (CMD_PUSH, CMD_PULL):
                assert fl == 0

        core.trace_enable(True)
        with srv.lock:
            srv.frames.clear()
        np.testing.assert_array_equal(sess.push_pull(9, x), x)  # round 1
        with srv.lock:
            frames = list(srv.frames)
        pp = [(c, f) for _, c, f in frames if c in (CMD_PUSH, CMD_PULL)]
        assert pp and all(f == (1 & 0x7FFF) | FLAG_TRACED for _, f in pp)
    finally:
        core.trace_enable(False)
        if sess is not None:
            sess.close()
        srv.close()
        if core.trace_count():    # don't leak spans into later tests
            core.trace_dump(str(tmp_path / "flush.json"), 0)


def test_server_spans_gated_by_trace_window(ps_server, tmp_path):  # noqa: F811
    """The server records spans ONLY for pushes carrying the traced flag
    (the worker's window): untraced rounds leave the ring empty, traced
    rounds produce RECV/SUM/MERGE_WAIT/PUBLISH/PULL_SEND per (key, round),
    and CMD_TRACE is fetch-and-clear."""
    port = ps_server(num_workers=1)
    core = get_core()
    sess = PSSession(["127.0.0.1"], [port], worker_id=0, num_servers=1,
                     partition_bytes=4096)
    try:
        x = np.arange(2048, dtype=np.float32)        # 2 partitions
        core.trace_enable(False)
        sess.push_pull(11, x)                        # untraced round
        assert sess.fetch_server_trace() == []

        core.trace_enable(True)
        t0 = core.trace_now_us()
        sess.push_pull(11, x)                        # traced round
        t1 = core.trace_now_us()
        spans = sess.fetch_server_trace()
        by_stage = {}
        for s in spans:
            by_stage.setdefault(s["stage"], []).append(s)
        for stage in ("RECV", "SUM", "MERGE_WAIT", "PUBLISH", "PULL_SEND"):
            rows = by_stage.get(stage, [])
            assert len(rows) == 2, (stage, sorted(by_stage))
            for r in rows:
                assert r["key"] >> 16 == 11
                assert r["worker"] == 0
                assert r["dur_us"] >= 0
                # Aligned clock: the offset-corrected server timestamps
                # land inside the worker-side bracket of the operation.
                assert t0 - 10_000 <= r["ts_us"] <= t1 + 10_000
        # Drain semantics: a second fetch starts empty again.
        assert sess.fetch_server_trace() == []
    finally:
        core.trace_enable(False)
        sess.close()
        if core.trace_count():
            core.trace_dump(str(tmp_path / "flush.json"), 0)


def test_old_server_cmd_trace_graceful():
    """Against a pre-CMD_TRACE server the fetch raises a clean 'server
    too old' RuntimeError promptly — never a hang.  (The offset-
    estimation leg hits it first: old PING answers 0 bytes.)"""
    def old_handler(cmd, dt, fl, req_id, wid, key, payload):
        if cmd == CMD_HELLO:
            return 0, b"\x00\x00"
        if cmd == CMD_PING:
            return 0, b""        # the OLD ping: empty, flags ignored
        return 1, b""            # pre-CMD_TRACE engine default arm

    srv = StubPSServer(old_handler)
    try:
        s = PSSession(["127.0.0.1"], [srv.port], worker_id=0,
                      num_servers=1, wire_conns=1)
        t0 = time.time()
        with pytest.raises(RuntimeError, match="too old"):
            s.fetch_server_trace(timeout=20.0)
        assert time.time() - t0 < 10, "error path took too long"
        s.close()
    finally:
        srv.close()


def test_trace_analyze_breakdown_sums_to_step():
    """Analyzer unit test on synthetic events: the per-step breakdown
    components take their measured values, partition the step exactly
    (sum == step duration), and the MERGE_WAIT group attributes the
    stragglers' cost to the last-merging worker."""
    SP = trace_analysis.SERVER_PID_BASE
    key = 7 << 16

    def w(tid, ts, dur, **args):
        return {"name": "g.part0", "ph": "X", "tid": tid, "pid": 0,
                "ts": ts, "dur": dur,
                "args": dict({"key": key, "bytes": 100, "priority": 0},
                             **args)}

    def s(tid, ts, dur, worker):
        return {"name": "g.part0", "ph": "X", "tid": tid, "pid": SP,
                "ts": ts, "dur": dur,
                "args": {"key": key, "round": 0, "worker": worker,
                         "bytes": 100}}

    events = [
        {"name": "process_name", "ph": "M", "pid": 0,
         "args": {"name": "worker0"}},
        {"name": "step_1", "ph": "X", "tid": "STEP", "pid": 0,
         "ts": 0, "dur": 1000},
        w("QUEUE", 10, 50),
        w("PUSH", 60, 200),
        w("PULL", 260, 400),
        s("RECV", 70, 20, 0),
        s("SUM", 90, 30, 0),
        s("MERGE_WAIT", 120, 300, 0),    # we waited 300us on worker 1
        s("MERGE_WAIT", 420, 0, 1),      # worker 1 merged last: straggler
        s("PUBLISH", 420, 5, 1),
    ]
    result = trace_analysis.analyze(events, worker=0)
    (row,) = result["steps"]
    bd = row["breakdown_us"]
    assert bd["queue"] == 50
    assert bd["server_recv"] == 20
    assert bd["server_sum"] == 30
    assert bd["merge_wait"] == 300
    assert bd["push_wire"] == 200 - 20 - 30
    assert bd["pull_wire"] == 400 - 300
    assert sum(bd.values()) == row["dur_us"] == 1000
    assert not row["normalized"]
    assert row["critical"] == "g.part0"
    # Straggler attribution: worker 1 (min wait in the group) caused
    # worker 0's 300us of merge wait.
    assert result["straggler_wait_us"] == {1: 300}
    assert result["top_blocking"][0]["name"] == "g"
    # The gauges feed a registry without touching the process-global one.
    from byteps_tpu.common.telemetry import MetricsRegistry
    reg = MetricsRegistry()
    trace_analysis.update_critical_path_gauges(result, registry=reg)
    g = reg.gauge("bps_step_critical_path_seconds",
                  labels={"component": "merge_wait"})
    assert g.value() == pytest.approx(300 / 1e6)
    sw = reg.gauge("bps_step_straggler_wait_seconds",
                   labels={"worker": "1"})
    assert sw.value() == pytest.approx(300 / 1e6)
    # A later window where nobody straggles must ZERO the stale label —
    # "the last analyzed trace window" means exactly that.
    clean = dict(result, straggler_wait_us={})
    trace_analysis.update_critical_path_gauges(clean, registry=reg)
    assert sw.value() == 0


def test_trace_analyze_members_and_normalization():
    """Fused-bucket spans carry args.members into the blocking report,
    and a chain longer than its step envelope normalizes so the
    breakdown still sums exactly to the step time."""
    key = 3 << 16
    events = [
        {"name": "step_2", "ph": "X", "tid": "STEP", "pid": 0,
         "ts": 0, "dur": 100},
        {"name": "t.fb0.f32x100.abc.part0", "ph": "X", "tid": "QUEUE",
         "pid": 0, "ts": 0, "dur": 80,
         "args": {"key": key, "bytes": 400, "priority": 9,
                  "members": ["t['a']", "t['b']"]}},
        {"name": "t.fb0.f32x100.abc.part0", "ph": "X", "tid": "PUSH",
         "pid": 0, "ts": 80, "dur": 80,
         "args": {"key": key, "bytes": 400, "priority": 9}},
    ]
    result = trace_analysis.analyze(events, worker=0)
    (row,) = result["steps"]
    assert row["normalized"]
    assert sum(row["breakdown_us"].values()) == row["dur_us"] == 100
    top = result["top_blocking"][0]
    assert top["name"] == "t.fb0.f32x100.abc"
    assert top["members"] == ["t['a']", "t['b']"]


def test_merged_trace_two_worker_acceptance(ps_server, tmp_path):  # noqa: F811
    """ISSUE-5 acceptance: a 2-worker PS run with BYTEPS_TRACE_ON=1
    produces ONE merged Chrome/Perfetto file holding worker AND server
    spans on an aligned clock; trace_analyze's per-step breakdown sums
    to the measured step time; the straggler worker is attributed."""
    import subprocess
    import sys
    port = ps_server(num_workers=2)
    code = """
import time
import numpy as np, jax.numpy as jnp
import byteps_tpu as bps
bps.init()
for step in range(4):
    if bps.rank() == 1 and step >= 1:
        time.sleep(0.12)      # worker 1 straggles inside the window
    bps.push_pull(jnp.ones(5000), name="g", average=False)
    bps.mark_step()
bps.shutdown()
"""
    procs = []
    for wid in (0, 1):
        env = cpu_env({
            "BYTEPS_TPU_PS_MODE": "1",
            "DMLC_NUM_WORKER": "2",
            "DMLC_WORKER_ID": str(wid),
            "DMLC_NUM_SERVER": "1",
            "DMLC_PS_ROOT_PORT": str(port - 1),
            "BYTEPS_TRACE_ON": "1",
            "BYTEPS_TRACE_DIR": str(tmp_path / f"w{wid}"),
            "BYTEPS_TRACE_START_STEP": "1",
            # Worker 0 closes its window (and drains the server ring)
            # strictly before worker 1's shutdown-time dump: w0 dumps at
            # its step-3 mark_step, which precedes its step-4 push, which
            # gates w1's step-4 round.
            "BYTEPS_TRACE_END_STEP": "2" if wid == 0 else "3",
            "BYTEPS_PARTITION_BYTES": "4096",
            "BYTEPS_LOG_LEVEL": "ERROR",
        })
        procs.append(subprocess.Popen(
            [sys.executable, "-c", code], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    for p in procs:
        out, err = p.communicate(timeout=240)
        assert p.returncode == 0, err[-3000:]

    with open(tmp_path / "w0" / "0" / "comm.json") as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    # Chrome/Perfetto schema: every event well-formed.
    for e in events:
        assert e.get("ph") in ("X", "M"), e
        assert "name" in e and "pid" in e
        if e["ph"] == "X":
            assert isinstance(e["ts"], (int, float))
            assert e.get("dur", 0) >= 0
            assert "tid" in e
    names = {e["name"] for e in events if e["ph"] == "M"}
    assert "process_name" in names
    SP = trace_analysis.SERVER_PID_BASE
    worker_spans = [e for e in events if e["ph"] == "X" and e["pid"] < SP]
    server_spans = [e for e in events if e["ph"] == "X" and e["pid"] >= SP]
    assert {e["tid"] for e in worker_spans} >= {"STEP", "QUEUE", "PUSH",
                                               "PULL"}
    sstages = {e["tid"] for e in server_spans}
    assert {"RECV", "SUM", "MERGE_WAIT", "PUBLISH", "PULL_SEND"} <= sstages
    # MERGE_WAIT attributes both workers — the server saw the fleet.
    mw_workers = {e["args"]["worker"] for e in server_spans
                  if e["tid"] == "MERGE_WAIT"}
    assert mw_workers == {0, 1}
    # Aligned clock: server spans sit inside the worker timeline (with
    # slack for the straggler sleep).
    wlo = min(e["ts"] for e in worker_spans)
    whi = max(e["ts"] + e.get("dur", 0) for e in worker_spans)
    for e in server_spans:
        assert wlo - 1_000_000 <= e["ts"] <= whi + 1_000_000

    # Critical-path analysis: breakdown partitions each step exactly,
    # and worker 1's 120ms sleep shows up as merge wait charged to it.
    result = trace_analysis.analyze(events, worker=0)
    assert result["steps"], "no STEP envelopes analyzed"
    for row in result["steps"]:
        assert sum(row["breakdown_us"].values()) == row["dur_us"]
    assert max(r["breakdown_us"]["merge_wait"]
               for r in result["steps"]) > 50_000
    sw = result["straggler_wait_us"]
    assert sw.get(1, 0) > sw.get(0, 0)
    # The CLI renders the same result.
    report = trace_analysis.format_report(result)
    assert "merge_wait" in report and "worker 1" in report


def test_fusion_bucket_members_in_merged_trace(ps_server, tmp_path):  # noqa: F811
    """Satellite: fused-bucket spans in the merged file carry their
    member-leaf names in args.members, so a slow bucket is attributable
    to real parameters."""
    import subprocess
    import sys
    port = ps_server(num_workers=1)
    code = """
import numpy as np, jax.numpy as jnp
import byteps_tpu as bps
bps.init()
tree = {"a": jnp.ones(100), "b": jnp.ones(200), "c": jnp.ones(300)}
for step in range(3):
    bps.push_pull_tree(tree, name="t7", average=False)
    bps.mark_step()
bps.shutdown()
"""
    env = cpu_env({
        "BYTEPS_TPU_PS_MODE": "1",
        "DMLC_NUM_WORKER": "1",
        "DMLC_NUM_SERVER": "1",
        "DMLC_PS_ROOT_PORT": str(port - 1),
        "BYTEPS_TRACE_ON": "1",
        "BYTEPS_TRACE_DIR": str(tmp_path),
        "BYTEPS_TRACE_START_STEP": "0",
        "BYTEPS_TRACE_END_STEP": "1",
        "BYTEPS_LOG_LEVEL": "ERROR",
    })
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stderr[-3000:]
    with open(tmp_path / "0" / "comm.json") as f:
        events = json.load(f)["traceEvents"]
    bucket = [e for e in events if e.get("ph") == "X"
              and ".fb0." in e.get("name", "")
              and (e.get("args") or {}).get("members")]
    assert bucket, "no fused-bucket span carries args.members"
    members = bucket[0]["args"]["members"]
    assert len(members) == 3
    assert all("t7" in m for m in members)
    # The compile lane (utils/compile_cache.py): this job is traced from
    # step 0, so the programs its first round compiles op by op lie
    # inside that ROUND, on the one clock; the second round makes none.
    spans = [e for e in events if e.get("ph") == "X"]
    first, second = sorted((e for e in spans if e["tid"] == "ROUND"),
                           key=lambda e: e["ts"])
    compiles = [e for e in spans if e["tid"] == "COMPILE"
                and e["pid"] == trace_analysis.COMPILE_PID_BASE]

    def inside(rnd):
        return [e for e in compiles if rnd["ts"] <= e["ts"]
                and e["ts"] + e["dur"] <= rnd["ts"] + rnd["dur"]]
    assert inside(first) and not inside(second)
    assert all(e["args"]["cache"] == "uncached" for e in compiles)
    told = trace_analysis.analyze(events)["compile_log"]
    assert told["spans"] >= len(compiles) > 0
    assert told["totals"]["by_cache"]["uncached"] >= len(compiles)
    # set-up ended where the second call began, just before its ROUND
    assert abs(told["steady_at_us"] - second["ts"]) < 50_000
    assert "compile log of the process" in trace_analysis.format_report(
        {"compile_log": told})


# ---------------------------------------------------------------------------
# The round from inside: main-thread stage spans (ISSUE 26)
# ---------------------------------------------------------------------------
_ROUND_JOB = """
import json, os, jax, jax.numpy as jnp
import byteps_tpu as bps
from byteps_tpu.core.native import get_core
from byteps_tpu.server import wire_floor
start_peer = wire_floor._start_peer
wire_floor._start_peer = lambda *a: print("PEER_STARTED") or start_peer(*a)
bps.init()
tree = {"a": jnp.ones((100, 3)), "b": jnp.ones(200),
        "c": jnp.full((300000,), 2.0), "d": jnp.ones((7, 5))}
for step in range(4):
    jax.block_until_ready(
        bps.push_pull_tree(tree, name="t26", average=False))
    if step == 0:
        print("COUNT_OUTSIDE_WINDOW", get_core().trace_count())
    bps.mark_step()
trace_dir = os.environ.get("BYTEPS_TRACE_DIR")
bps.shutdown()
floor = trace_dir and os.path.join(trace_dir, "0", "wire_floor.json")
print("WIRE_FLOOR", json.dumps(json.load(open(floor)))
      if floor and os.path.isfile(floor) else None)
from byteps_tpu.common import telemetry
print(telemetry.get_registry().render_prometheus())
"""


def _run_round_job(port, tmp_path, fusion_bytes, trace_on=True):
    import subprocess
    import sys
    env = {
        "BYTEPS_TPU_PS_MODE": "1", "DMLC_NUM_WORKER": "1",
        "DMLC_NUM_SERVER": "1", "DMLC_PS_ROOT_PORT": str(port - 1),
        "BYTEPS_PARTITION_BYTES": "65536", "BYTEPS_LOG_LEVEL": "ERROR",
    }
    if trace_on:
        env.update({"BYTEPS_TRACE_ON": "1",
                    "BYTEPS_TRACE_DIR": str(tmp_path),
                    "BYTEPS_TRACE_START_STEP": "1",
                    "BYTEPS_TRACE_END_STEP": "2"})
    if fusion_bytes is not None:
        env["BYTEPS_TPU_FUSION_BYTES"] = fusion_bytes
    r = subprocess.run([sys.executable, "-c", _ROUND_JOB], env=cpu_env(env),
                       capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


_ROUND_EVENTS: dict = {}    # one recorded job per branch, for all its tests


@pytest.fixture(params=[None, "0"], ids=["fused", "unfused"])
def round_events(request, ps_server, tmp_path):  # noqa: F811
    """The merged comm.json of two traced `push_pull_tree` rounds with
    the default BYTEPS_TPU_FUSION_BYTES (fused: the small leaves in one
    bucket) or with 0 (unfused: no packing, a unit a leaf), and the
    job's output."""
    if request.param not in _ROUND_EVENTS:
        port = ps_server(num_workers=1)
        out = _run_round_job(port, tmp_path, request.param)
        with open(tmp_path / "0" / "comm.json") as f:
            events = [e for e in json.load(f)["traceEvents"]
                      if e.get("ph") == "X"]
        _ROUND_EVENTS[request.param] = events, out
    return _ROUND_EVENTS[request.param]


def _end(e):
    return e["ts"] + e["dur"]


def _children(events, rnd):
    from byteps_tpu.common import stage_spans
    return [e for e in events if e["tid"] in stage_spans.STAGES[1:]
            and e["args"]["round"] == rnd["args"]["round"]]


def test_round_spans_one_round_per_call(round_events):
    events, out = round_events
    rounds = [e for e in events if e["tid"] == "ROUND"]
    # steps 1 and 2 are the window: one ROUND per call, numbered apart
    assert len(rounds) == 2
    assert len({r["args"]["round"] for r in rounds}) == 2
    assert all(r["args"]["round"] > 0 and r["name"] == "t26"
               for r in rounds)
    assert "COUNT_OUTSIDE_WINDOW 0" in out


def test_round_spans_children_inside_and_disjoint(round_events, request):
    events, _ = round_events
    from byteps_tpu.common import stage_spans
    staged = [e for e in events if e["tid"] in stage_spans.STAGES[1:]]
    rounds = [e for e in events if e["tid"] == "ROUND"]
    # every stage span of the window names one of its ROUNDs
    assert {e["args"]["round"] for e in staged} == {
        r["args"]["round"] for r in rounds}
    for rnd in rounds:
        kids = sorted(_children(events, rnd), key=lambda e: e["ts"])
        # One round, whatever the threshold: the same kinds of span in
        # the same order, a unit staged at a time and then a unit
        # collected at a time; only the number of units differs (two
        # with the default, a bucket and the large leaf; four leaves
        # with no packing).
        units = rnd["args"]["units"]
        no_packing = request.node.callspec.params["round_events"] == "0"
        assert units == (4 if no_packing else 2)
        kinds = [e["tid"] for e in kids if e["tid"] != "PACK"]
        assert kinds == (["D2H", "STAGE", "STAGE"] * units
                         + ["WAIT", "H2D", "SCATTER"] * units + ["FREE"])
        assert [e["tid"] for e in kids][:3] == ["PACK"] * 3
        assert all(rnd["ts"] <= e["ts"] and _end(e) <= _end(rnd)
                   for e in kids)
        assert all(_end(a) <= b["ts"] for a, b in zip(kids, kids[1:]))


def test_round_spans_count_the_tree(round_events):
    events, _ = round_events
    tree_bytes = 4 * (300 + 200 + 300000 + 35)
    for rnd in (e for e in events if e["tid"] == "ROUND"):
        kids = _children(events, rnd)
        d2h = [e for e in kids if e["tid"] == "D2H"]
        assert sum(e["args"]["bytes"] for e in d2h) == tree_bytes
        a = rnd["args"]
        assert a["units"] == len(d2h)
        assert a["bytes_out"] == a["bytes_in"] == tree_bytes
        assert set(a) == {"round", "units", "units_early", "bytes_out",
                          "bytes_in", "minflt", "lanes", "lane_busy_us",
                          *PSSession.WIRE_COUNTS}
        # the process's minor page faults while the ROUND was open
        assert isinstance(a["minflt"], int) and a["minflt"] >= 0
        # a group queues each unit before the next one's copy begins
        assert a["units_early"] == (a["units"] - 1 if a["units"] > 1 else 0)
        # a unit's key is its partitions' key above bit 16
        parts = [e for e in events if e["tid"] == "PUSH"
                 and rnd["ts"] <= e["ts"] < _end(rnd)]
        waited = {e["args"]["key"] for e in kids if e["tid"] == "WAIT"}
        assert waited == {e["args"]["key"] >> 16 for e in parts}
        for stage in ("D2H", "H2D", "SCATTER"):
            assert {e["args"]["key"] for e in kids
                    if e["tid"] == stage} == waited


def test_round_counts_what_the_wire_waited_for(round_events):
    """A traced ROUND carries what the session's lanes counted while it
    was open (docs/timeline.md, "The round from inside")."""
    events, _ = round_events
    for rnd in (e for e in events if e["tid"] == "ROUND"):
        a = rnd["args"]
        pushes = [e for e in events if e["tid"] == "PUSH"
                  and rnd["ts"] <= e["ts"] < _end(rnd)]
        # a push and a pull request a partition at the least, and a
        # header and a payload received for the pull
        assert a["send_calls"] >= 2 * len(pushes) > 0
        assert a["recv_calls"] >= 2 * len(pushes)
        assert a["pulls"] == len(pushes)
        for k in ("send_lock_wait_us", "send_us", "recv_us",
                  "recv_first_byte_us"):
            assert isinstance(a[k], int) and 0 <= a[k], k
        # the socket calls are inside the round, on `lanes` threads
        # that send and as many that receive
        assert a["send_us"] + a["recv_us"] <= 2 * a["lanes"] * rnd["dur"]
        assert a["lanes"] == 4 == len(a["lane_busy_us"])
        assert all(0 <= b <= rnd["dur"] for b in a["lane_busy_us"])
        assert sum(a["lane_busy_us"]) > 0
    # the analyzer's mean per round, and its report
    rounds = [e["args"] for e in events if e["tid"] == "ROUND"]
    result = trace_analysis.analyze(events, worker=0)
    got = result["round_wire"]
    assert set(got) == {"lanes", "lane_busy_us", "lanes_sending",
                        *PSSession.WIRE_COUNTS}
    assert got["send_calls"] == sum(
        a["send_calls"] for a in rounds) // len(rounds)
    assert got["lane_busy_us"] == [
        sum(a["lane_busy_us"][i] for a in rounds) // len(rounds)
        for i in range(4)]
    assert "what the wire waited for" in trace_analysis.format_report(
        result)
    for e in events:        # a program whose ROUND carries none
        if e["tid"] == "ROUND":
            e = dict(e, args={"round": e["args"]["round"]})
            assert trace_analysis.round_wire([e]) == {}


def test_round_counts_who_sent_its_pushes(round_events):
    """A traced ROUND carries the sender-a-lane counts: every push of it
    left through a lane's sender, some sender was inside a sending call
    for no longer than the round, and `send_us` over that time, the
    lanes sending at once, lies between one and the lanes."""
    events, _ = round_events
    rounds = [e for e in events if e["tid"] == "ROUND"]
    for rnd in rounds:
        a = rnd["args"]
        pushes = [e for e in events if e["tid"] == "PUSH"
                  and rnd["ts"] <= e["ts"] < _end(rnd)]
        assert a["push_handoffs"] == len(pushes) > 0
        assert 0 < a["send_wall_us"] <= rnd["dur"]
        assert a["send_wall_us"] <= a["send_us"] \
            <= a["lanes"] * a["send_wall_us"]
        assert isinstance(a["handoff_wait_us"], int)
        assert 0 <= a["handoff_wait_us"] <= rnd["dur"]
    result = trace_analysis.analyze(events, worker=0)
    got = result["round_wire"]
    assert got["push_handoffs"] == sum(
        r["args"]["push_handoffs"] for r in rounds) // len(rounds)
    assert 1.0 <= got["lanes_sending"] <= got["lanes"]
    assert got["lanes_sending"] == pytest.approx(
        sum(r["args"]["send_us"] for r in rounds)
        / sum(r["args"]["send_wall_us"] for r in rounds), abs=1e-3)
    report = trace_analysis.format_report(result)
    for word in ("lanes_sending", "push_handoffs", "handoff_wait"):
        assert word in report
    # a program whose ROUND has no `send_wall_us` (before the senders)
    old = [dict(e, args={k: v for k, v in e["args"].items()
                         if k != "send_wall_us"}) for e in rounds]
    assert "lanes_sending" not in trace_analysis.round_wire(old)


def test_trace_analyze_round_wire_prints_lanes_sending():
    """`tools/trace_analyze.py`'s `round_wire` on two recorded ROUNDs:
    means a round, and the senders' three counts as it prints them."""
    def rnd(n, send_us, wall_us, handoffs, wait_us):
        return {"ph": "X", "pid": 0, "tid": "ROUND", "name": "t", "ts": n,
                "dur": 1000, "args": {
                    "round": n, "units": 1, "units_early": 0,
                    "bytes_out": 8, "bytes_in": 8, "minflt": 0,
                    "send_calls": 4, "recv_calls": 8,
                    "send_lock_wait_us": 2, "send_us": send_us,
                    "recv_us": 50, "recv_first_byte_us": 20, "pulls": 2,
                    "push_handoffs": handoffs, "send_wall_us": wall_us,
                    "handoff_wait_us": wait_us, "lanes": 4,
                    "lane_busy_us": [10, 20, 30, 40]}}
    got = trace_analysis.round_wire([rnd(1, 900, 300, 340, 250),
                                     rnd(2, 700, 340, 342, 150)])
    assert got["lanes_sending"] == 2.5            # 1600 / 640
    assert got["push_handoffs"] == 341 and got["handoff_wait_us"] == 200
    assert got["send_wall_us"] == 320 and got["lanes"] == 4
    report = trace_analysis.format_report({"round_wire": got})
    lines = {l.split()[0]: l.split()[1:] for l in report.splitlines()[1:]}
    assert lines["lanes_sending"] == ["2.5"]
    assert lines["push_handoffs"] == ["341"]
    assert lines["handoff_wait"] == ["200us"]


def test_a_traced_worker_leaves_its_floor_beside_comm_json(
        round_events, tmp_path_factory):
    """`bps.shutdown()` of the traced job probed the floor over the
    session's own lanes (server/wire_floor.py)."""
    _, out = round_events
    assert out.count("PEER_STARTED") == 1
    floor = json.loads(out.split("WIRE_FLOOR ", 1)[1].splitlines()[0])
    assert (floor["transport"], floor["lanes"], floor["frame_bytes"],
            floor["sock_buf_kb"]) == ("tcp", 4, 65536, 0)
    tree_bytes = 4 * (300 + 200 + 300000 + 35)
    for direction in ("out", "in", "duplex"):
        got = floor[direction]
        # the last traced round's bytes, in whole frames a lane
        assert tree_bytes <= got["bytes"] / (1 + (direction == "duplex")) \
            <= tree_bytes + 4 * 65536
        assert got["GB_per_s"] == pytest.approx(
            got["bytes"] / got["seconds"] / 1e9)
    assert 'bps_wire_floor_gbps{dir="duplex"} ' + repr(
        floor["duplex"]["GB_per_s"]) in out


def test_an_untraced_round_reads_no_clock_and_starts_no_child(
        ps_server, tmp_path, monkeypatch):  # noqa: F811
    """With the tracer off the wire's counters make no clock read (they
    all read `client._now_us`), a ROUND counts nothing, and shutdown
    probes no floor; the calls are counted all the same."""
    from byteps_tpu.server import client

    def no_clock():
        raise AssertionError("a clock read with the tracer off")

    monkeypatch.setattr(client, "_now_us", no_clock)
    sess = PSSession(["127.0.0.1"], [ps_server(num_workers=1)], worker_id=0,
                     num_servers=1, partition_bytes=65536)
    try:
        x = np.arange(100000, dtype=np.float32)
        with sess.spans.round("untraced"):
            np.testing.assert_array_equal(sess.push_pull(7, x), x)
        assert sess.spans.last is None
        stats = sess.transport_stats()
        assert set(PSSession.WIRE_COUNTS) <= set(stats)
        assert set(PSSession.WIRE_COUNTS) <= set(
            PSSession.TRANSPORT_ZERO_STATS)
        parts = -(-x.nbytes // 65536)
        assert stats["send_calls"] >= 2 * parts
        assert stats["recv_calls"] >= 2 * parts
        assert stats["push_handoffs"] == parts    # on the senders' threads
        assert all(stats[k] == 0 for k in PSSession.WIRE_COUNTS
                   if k.endswith("_us") or k == "pulls")
        assert sum(r["send_calls"] for r in stats["lanes"]) \
            == stats["send_calls"]
        assert all(r["busy_us"] == 0 for r in stats["lanes"])
    finally:
        sess.close()
    # the untraced twin of the traced job: no file, no child
    out = _run_round_job(ps_server(num_workers=1), tmp_path, None,
                         trace_on=False)
    assert "WIRE_FLOOR None" in out and "PEER_STARTED" not in out
    assert not list(tmp_path.rglob("*.json"))


def test_round_breakdown_sums_to_the_round(round_events):
    events, _ = round_events
    got = trace_analysis.analyze(events, worker=0)["round_breakdown_us"]
    assert set(got) == {"round", "pack", "d2h", "stage", "wait", "h2d",
                        "scatter", "free", "unspanned"}
    assert got["round"] == sum(v for k, v in got.items() if k != "round")
    assert got["unspanned"] >= 0
    rounds = [e for e in events if e["tid"] == "ROUND"]
    assert got["round"] == sum(r["dur"] for r in rounds) // len(rounds)
    # a stage written with no ROUND open (round 0) is not counted
    stray = {"ph": "X", "pid": 0, "tid": "WAIT", "name": "x", "ts": 0,
             "dur": 10**9, "args": {"round": 0, "key": 1}}
    assert trace_analysis.round_breakdown(events + [stray]) == got
    assert trace_analysis.analyze(
        [e for e in events if e["tid"] != "ROUND"])[
            "round_breakdown_us"] == {}
    assert "per-round breakdown" in trace_analysis.format_report(
        trace_analysis.analyze(events))


def test_profiler_offset_from_a_recorded_pair():
    """benchmark/tests/data/spans: three traced rounds recorded on the
    CPU, comm.json and the profiler's .xplane.pb of the same run.  The
    offset comes from the ROUNDs alone; the stages under them, which
    took no part in it, must then meet their own annotations to within
    the stated spread and the few microseconds between entering an
    annotation and reading the program's clock."""
    import os
    from jax.profiler import ProfileData
    data = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "tests", "data", "spans")
    xplane = os.path.join(data, "plugins", "profile", "tiny",
                          "tiny.xplane.pb")
    with open(os.path.join(data, "0", "comm.json")) as f:
        events = json.load(f)["traceEvents"]
    clock = trace_analysis.profiler_offset(events, xplane)
    assert clock["rounds"] == 3 and clock["spread_us"] < 5
    host = [ev for plane in ProfileData.from_file(xplane).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events]
    for stage in ("D2H", "WAIT", "H2D"):
        program = sorted(e["ts"] for e in events if e.get("tid") == stage)
        profiler = sorted(ev.start_ns / 1e3 for ev in host
                          if ev.name == "byteps." + stage.lower())
        assert len(program) == len(profiler) == 6
        for us, there in zip(program, profiler):
            assert abs(us + clock["offset_us"] - there) <= (
                clock["spread_us"] + 10)
    # the same pair with the program's clock a second ahead: a known
    # offset, recovered to the microsecond
    ahead = trace_analysis.profiler_offset(
        [{**e, "ts": e["ts"] + 1_000_000} if "ts" in e else e
         for e in events], xplane)
    assert ahead["offset_us"] == pytest.approx(
        clock["offset_us"] - 1_000_000, abs=1e-3)
    assert ahead["spread_us"] == pytest.approx(clock["spread_us"], abs=1e-3)
    # no ROUND in common with the capture: no offset, not a zero
    assert trace_analysis.profiler_offset(
        [e for e in events if e.get("tid") != "ROUND"], xplane) is None
    assert "profiler's clock" in trace_analysis.format_report(
        {"profiler_offset": clock})


@pytest.mark.parametrize("fusion_bytes", [None, "0"],
                         ids=["fused", "unfused"])
def test_round_spans_off_is_off(ps_server, tmp_path,  # noqa: F811
                                fusion_bytes):
    """With BYTEPS_TRACE_ON unset a round records nothing and writes no
    file."""
    port = ps_server(num_workers=1)
    out = _run_round_job(port, tmp_path, fusion_bytes, trace_on=False)
    assert "COUNT_OUTSIDE_WINDOW 0" in out
    assert not (tmp_path / "0").exists()


def test_stage_spans_outside_a_round_carry_round_zero(ps_server, tracing,  # noqa: F811
                                                      tmp_path):
    """`_stage` and `PSHandle.wait` reached with no ROUND open (a bare
    session, as AsyncPSTrainer and ServerOptTrainer drive it) write
    their spans with round 0."""
    from byteps_tpu.common import stage_spans
    port = ps_server(num_workers=1)
    sess = PSSession(["127.0.0.1"], [port], worker_id=0, num_servers=1)
    dk = get_core().num_declared() + 826
    sess.push_pull(dk, np.ones(64, np.float32))
    sess.close()
    events = _dump(tracing, tmp_path)
    mine = [e for e in events if e["tid"] in stage_spans.STAGES]
    assert {e["tid"] for e in mine} == {"D2H", "STAGE", "WAIT"}
    assert all(e["args"]["round"] == 0 for e in mine)
    assert not set(stage_spans.STAGES) & set(trace_analysis.WORKER_STAGES)
    assert trace_analysis.round_breakdown(events) == {}
