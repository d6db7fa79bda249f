"""CMD_STATS wire tests: server-side stats over the wire, round-lag
straggler signals, old-server compatibility, and the Prometheus endpoint
during a live multi-worker run (ISSUE-4 acceptance scenario).

Server harness mirrors tests/test_ps_server.py: the native KV server
runs as a subprocess, N PSSession workers drive it on threads.
"""

import json
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from byteps_tpu.common import telemetry as tm
from byteps_tpu.server.client import PSSession, _ServerConn, CMD_HELLO

from testutil import StubPSServer, cpu_env, free_port


@pytest.fixture
def ps_server():
    """Yields a start(num_workers=...) -> port callable; kills servers
    after (the test_ps_server harness, trimmed)."""
    made = []

    def start(num_workers=2, async_mode=False, extra_env=None):
        last = None
        for _ in range(3):   # free_port is bind-then-close TOCTOU: retry
            try:
                return _once(num_workers, async_mode, extra_env)
            except RuntimeError as e:
                last = e
        raise last

    def _once(num_workers, async_mode, extra_env):
        port = free_port()
        env = cpu_env({
            "DMLC_PS_ROOT_PORT": str(port - 1),
            "DMLC_NUM_WORKER": str(num_workers),
            "BYTEPS_SERVER_ENGINE_THREAD": "2",
            "BYTEPS_ENABLE_ASYNC": "1" if async_mode else "0",
            "JAX_PLATFORMS": "cpu",
            **(extra_env or {}),
        })
        proc = subprocess.Popen(
            [sys.executable, "-m", "byteps_tpu.server"], env=env,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        made.append(proc)
        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                socket.create_connection(("127.0.0.1", port), 0.5).close()
                return port
            except OSError:
                if proc.poll() is not None:
                    raise RuntimeError(f"server died rc={proc.returncode}")
                time.sleep(0.1)
        raise TimeoutError("PS server did not come up")

    yield start
    for p in made:
        p.kill()
        p.wait()


def _run_workers(port, n, fn):
    """Run fn(wid, session) on n threads, one PSSession each; the session
    is closed after.  Returns {wid: fn result}."""
    out, errs = {}, []

    def worker(wid):
        s = PSSession(["127.0.0.1"], [port], worker_id=wid, num_servers=1)
        try:
            out[wid] = fn(wid, s)
        except Exception as e:   # surface thread failures as test failures
            errs.append(e)
        finally:
            s.close()

    ts = [threading.Thread(target=worker, args=(w,)) for w in range(n)]
    [t.start() for t in ts]
    [t.join(timeout=120) for t in ts]
    assert not errs, errs
    return out


def test_cmd_stats_roundtrip(ps_server):
    """CMD_STATS reports per-key merge counts / completed rounds /
    pending depth, per-worker push counts and round position, and wire
    bytes in/out — all consistent with 2 workers x 3 rounds of one key."""
    port = ps_server(num_workers=2)
    a = np.arange(100, dtype=np.float32)
    barrier = threading.Barrier(2)

    def fn(wid, s):
        for _ in range(3):
            s.push_pull(7, a)
        barrier.wait(timeout=60)       # both workers fully done
        return s.server_stats()

    stats = _run_workers(port, 2, fn)[0]
    assert stats["num_workers"] == 2
    assert not stats["async"]
    assert stats["bytes_in"] > 0 and stats["bytes_out"] > 0
    wire_key = 7 << 16                 # declared key 7, partition 0
    ks = stats["keys"][wire_key]
    assert ks["completed_round"] == 3
    assert ks["merges"] == 6           # 2 workers x 3 rounds
    assert ks["pushes"] >= ks["merges"]
    assert ks["bytes"] == 6 * a.nbytes
    assert ks["pending_pulls"] == 0    # everything drained
    for wid in (0, 1):
        assert stats["workers"][wid]["pushes"] == 3
        assert stats["workers"][wid]["round"] == 3


def test_round_lag_visible_when_worker_trails(ps_server):
    """A worker that staged its round-r+1 push while a peer is still on
    round r shows up one round ahead in CMD_STATS; update_round_lag turns
    that into a nonzero bps_worker_round_lag gauge for the trailing
    worker."""
    port = ps_server(num_workers=2)
    a = np.ones(64, np.float32)
    w0_pushed_ahead = threading.Event()
    stats_box = {}

    def fn(wid, s):
        s.push_pull(3, a)              # round 0: both workers
        if wid == 0:
            h = s.push_pull_async(3, a)   # round 1: only w0 pushes
            # Wait until the server actually merged w0's round-1 push.
            deadline = time.time() + 30
            while time.time() < deadline:
                st = s.server_stats()
                if st["workers"].get(0, {}).get("round", 0) == 2:
                    stats_box.update(st)
                    break
                time.sleep(0.05)
            w0_pushed_ahead.set()
            # Unblock the handle: w1 joins round 1 below.
        else:
            assert w0_pushed_ahead.wait(timeout=60)
            s.push_pull(3, a)          # w1 joins round 1; round publishes
        if wid == 0:
            h.wait()

    _run_workers(port, 2, fn)
    assert stats_box, "never observed w0 a round ahead"
    assert stats_box["workers"][0]["round"] == 2
    assert stats_box["workers"][1]["round"] == 1
    reg = tm.MetricsRegistry()
    lags = tm.update_round_lag(stats_box, straggler_rounds=10, registry=reg)
    assert lags == {0: 0, 1: 1}
    assert reg.gauge("bps_worker_round_lag",
                     labels={"worker": "1"}).value() == 1


def test_pending_pull_depth_visible(ps_server):
    """A pull parked for an unpublished round shows as pending_pulls > 0
    — the 'workers are waiting on a straggler' depth signal."""
    port = ps_server(num_workers=2)
    a = np.ones(32, np.float32)
    seen = {}

    def fn(wid, s):
        if wid == 0:
            h = s.push_pull_async(5, a)    # w0 pushes+pulls; pull pends
            deadline = time.time() + 30
            while time.time() < deadline:
                st = s.server_stats()
                if st["keys"].get(5 << 16, {}).get("pending_pulls"):
                    seen.update(st)
                    break
                time.sleep(0.05)
            seen.setdefault("keys", {})
            s2_done.set()
            h_box.append(h)
        else:
            s2_done.wait(timeout=60)
            s.push_pull(5, a)              # completes the round
        if wid == 0:
            h_box[0].wait()

    s2_done = threading.Event()
    h_box = []
    _run_workers(port, 2, fn)
    ks = seen.get("keys", {}).get(5 << 16, {})
    assert ks.get("pending_pulls") == 1
    # Pending-push depth: w0 merged into the open round, w1 hadn't yet.
    assert ks.get("round_pushes") == 1


def test_old_server_graceful_too_old_error():
    """Against a server that predates CMD_STATS (unknown command answers
    with an error status), server_stats() raises a clean 'server too old'
    RuntimeError promptly — never a hang.  The stub speaks the
    pre-CMD_STATS protocol: HELLO answers mode flags, anything unknown
    answers status=1 (the old engine default arm)."""
    srv = StubPSServer(lambda cmd, *a: (0, b"\x00\x00")
                       if cmd == CMD_HELLO else (1, b""))
    try:
        s = PSSession(["127.0.0.1"], [srv.port], worker_id=0,
                      num_servers=1, wire_conns=1)
        t0 = time.time()
        with pytest.raises(RuntimeError, match="too old"):
            s.server_stats(timeout=20.0)
        assert time.time() - t0 < 10, "error path took too long"
        s.close()
    finally:
        srv.close()


def test_unknown_command_error_not_hang(ps_server):
    """The forward-compat half of the contract: the CURRENT server's
    engine answers any unknown command with an error status (what makes
    a future client against this server fail fast, exactly like
    CMD_STATS against an old one)."""
    port = ps_server(num_workers=1)
    conn = _ServerConn("127.0.0.1", port)
    try:
        with pytest.raises(RuntimeError, match="PS server error"):
            conn.request(200, timeout=20.0)
    finally:
        conn.close()


def test_metrics_endpoint_during_two_worker_run(ps_server):
    """ISSUE-4 acceptance: scrape the Prometheus endpoint during a
    2-worker training run; it must carry push RTT histograms, dispatcher
    queue depth, per-worker round lag (via CMD_STATS), and the
    fusion/codec/transport counters identical to the legacy
    get_*_stats() accessors."""
    import byteps_tpu as bps
    from byteps_tpu.common.api import _register_builtin_collectors

    _register_builtin_collectors()
    port = ps_server(num_workers=2)
    a = np.arange(4096, dtype=np.float32)
    sessions = {}
    done = {0: threading.Event(), 1: threading.Event()}
    release = threading.Event()

    def fn(wid, s):
        sessions[wid] = s
        for _ in range(3):
            s.push_pull(11, a * (wid + 1))
        done[wid].set()
        assert release.wait(timeout=120)   # hold the session open: the
        #                                    scrape below polls CMD_STATS

    exp = tm.TelemetryExporter(
        tm.get_registry(), port=free_port(),
        refresh=lambda: tm.update_round_lag(
            sessions[0].server_stats(), 10)).start()
    try:
        th = threading.Thread(
            target=lambda: _run_workers(port, 2, fn), daemon=True)
        th.start()
        # Wait for both workers to finish their rounds, then scrape.
        assert done[0].wait(timeout=120) and done[1].wait(timeout=120)
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{exp.port}/metrics", timeout=10
        ).read().decode()
        release.set()
        th.join(timeout=120)
    finally:
        release.set()
        exp.stop()
    # Hot-path worker-side signals.
    assert "# TYPE bps_push_rtt_seconds histogram" in body
    assert 'bps_push_rtt_seconds_bucket{le="+Inf"}' in body
    rtt_count = int(next(l for l in body.splitlines()
                         if l.startswith("bps_push_rtt_seconds_count")
                         ).split()[-1])
    assert rtt_count >= 6              # 2 workers x 3 rounds
    assert "bps_dispatch_queue_depth" in body
    assert "bps_dispatch_queue_wait_seconds_count" in body
    # Server-side round lag via CMD_STATS (both in step: lag 0).
    assert 'bps_worker_round_lag{worker="0"} 0' in body
    assert 'bps_worker_round_lag{worker="1"} 0' in body
    # Collector-backed counters identical to the legacy accessors.
    exported = {l.split()[0]: float(l.split()[1])
                for l in body.splitlines()
                if l and not l.startswith("#") and len(l.split()) == 2}
    for prefix, legacy in (("bps_codec_", bps.get_codec_stats()),
                           ("bps_transport_", bps.get_transport_stats()),
                           ("bps_fusion_", bps.get_fusion_stats())):
        for k, v in legacy.items():
            if not isinstance(v, (int, float)):
                # Non-numeric detail (e.g. the per-lane row list) is for
                # get_*_stats() readers; the collector exports numbers only.
                assert prefix + k not in exported, (prefix, k)
                continue
            assert exported[prefix + k] == v, (prefix, k)


def test_metrics_endpoint_scraped_while_rounds_run(ps_server):
    """The export plane under load: a scraper polls the endpoint (whose
    refresh asks the server for CMD_STATS on the worker's own session)
    while sync rounds are in flight.  Every round still returns its sum,
    scrapes land between the first round and the last, and the last body
    counts every round's push.  No timing is asserted."""
    port = ps_server(num_workers=1)
    sess = PSSession(["127.0.0.1"], [port], worker_id=0, num_servers=1)
    exp = tm.TelemetryExporter(
        tm.get_registry(), port=free_port(),
        refresh=lambda: tm.update_round_lag(sess.server_stats(), 10)).start()
    url = f"http://127.0.0.1:{exp.port}/metrics"
    stop, bodies = threading.Event(), []

    def rtt_count(body):
        return int(next(l for l in body.splitlines()
                        if l.startswith("bps_push_rtt_seconds_count")
                        ).split()[-1])

    def get():
        return urllib.request.urlopen(url, timeout=10).read().decode()

    def scrape():
        while not stop.is_set():
            bodies.append(get())

    x = np.arange(1 << 18, dtype=np.float32)
    scraper = threading.Thread(target=scrape, daemon=True)
    try:
        before = rtt_count(get())
        scraper.start()
        rounds = 0
        while rounds < 8 or len(bodies) < 3:
            np.testing.assert_array_equal(sess.push_pull(21, x), x)
            rounds += 1
            assert rounds < 2000, "the scraper never got a body"
        stop.set()
        scraper.join(timeout=30)
        assert not scraper.is_alive()
        last = get()
    finally:
        stop.set()
        exp.stop()
        sess.close()
    assert rtt_count(last) - before >= rounds
    assert 'bps_worker_round_lag{worker="0"} 0' in last
    # Bodies taken mid-run parse and only ever count upwards.
    counts = [rtt_count(b) for b in bodies]
    assert counts == sorted(counts) and counts[-1] <= rtt_count(last)


def test_api_metrics_endpoint_and_jsonl(ps_server):
    """API-level acceptance: BYTEPS_TPU_METRICS_PORT + _METRICS_LOG wired
    through bps.init() — the endpoint serves during a PS-mode run with
    compressed traffic (codec counters hot), values match the legacy
    accessors, get_server_stats() reaches the server, and shutdown leaves
    a JSONL snapshot behind."""
    port = ps_server(num_workers=1)
    mport = free_port()
    code = """
import json, os, urllib.request
import numpy as np, jax.numpy as jnp
import byteps_tpu as bps
bps.init()
bps.register_compressor("tele.g", {"compressor": "onebit"})
x = jnp.asarray(np.linspace(-1, 1, 262144, dtype=np.float32))
for _ in range(2):
    bps.push_pull(x, name="tele.g", average=False)
    bps.mark_step()
st = bps.get_server_stats()
assert st["workers"][0]["pushes"] >= 2, st
assert st["bytes_in"] > 0
assert st["round_lag"] == {0: 0}, st
mport = int(os.environ["BYTEPS_TPU_METRICS_PORT"])
body = urllib.request.urlopen(
    f"http://127.0.0.1:{mport}/metrics", timeout=10).read().decode()
assert "bps_push_rtt_seconds_count" in body
assert "bps_worker_round_lag" in body
exported = {l.split()[0]: float(l.split()[1]) for l in body.splitlines()
            if l and not l.startswith("#") and len(l.split()) == 2}
codec = bps.get_codec_stats()
assert codec["encoded_parts"] > 0          # compression actually ran
for k in ("encoded_parts", "decoded_parts"):
    assert exported["bps_codec_" + k] == codec[k], k
speed = bps.get_pushpull_speed()[1]
assert speed > 0
bps.shutdown()
print("TELEMETRY_API_OK")
"""
    jsonl = f"/tmp/bps_metrics_{mport}.jsonl"
    env = cpu_env({
        "BYTEPS_TPU_PS_MODE": "1",
        "DMLC_NUM_WORKER": "1",
        "DMLC_NUM_SERVER": "1",
        "DMLC_PS_ROOT_PORT": str(port - 1),
        "BYTEPS_MIN_COMPRESS_BYTES": "0",
        "BYTEPS_TPU_METRICS_PORT": str(mport),
        "BYTEPS_TPU_METRICS_LOG": jsonl,
    })
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "TELEMETRY_API_OK" in proc.stdout
    with open(jsonl) as f:
        lines = [json.loads(l) for l in f.read().splitlines()]
    assert lines, "shutdown() must leave a final JSONL snapshot"
    last = lines[-1]["metrics"]
    assert last["bps_pushpull_bytes_total"] > 0
    assert last["bps_push_rtt_seconds"]["count"] > 0


def test_bps_top_parses_live_endpoint(ps_server):
    """tools/bps_top.py --once renders a snapshot from a live endpoint
    (parser + quantile math against real exposition output)."""
    import os
    tools_dir = os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools")
    if tools_dir not in sys.path:
        sys.path.insert(0, tools_dir)
    import bps_top

    reg = tm.MetricsRegistry()
    reg.counter("bps_pushpull_bytes_total").inc(1 << 20)
    h = reg.histogram("bps_push_rtt_seconds", bounds=(0.001, 0.01, 0.1))
    for v in (0.002, 0.002, 0.05):
        h.observe(v)
    reg.gauge("bps_worker_round_lag", labels={"worker": "1"}).set(3)
    reg.gauge("bps_step_critical_path_seconds",
              labels={"component": "merge_wait"}).set(0.2)
    reg.gauge("bps_step_critical_path_seconds",
              labels={"component": "push_wire"}).set(0.05)
    exp = tm.TelemetryExporter(reg, port=free_port()).start()
    try:
        text = bps_top.fetch(f"http://127.0.0.1:{exp.port}/metrics")
    finally:
        exp.stop()
    metrics = bps_top.parse(text)
    assert bps_top._get(metrics, "bps_pushpull_bytes_total") == 1 << 20
    p50 = bps_top.quantile(metrics, "bps_push_rtt_seconds", 0.5)
    assert 0.001 <= p50 <= 0.01
    lines = bps_top.render(metrics, {}, 1.0)
    joined = "\n".join(lines)
    assert "push RTT" in joined
    assert "worker   1  lag    3" in joined
    # Critical-path panel (bps_step_critical_path_* gauges, ISSUE-5).
    assert "step critical path" in joined
    assert "merge_wait" in joined and "push_wire" in joined
