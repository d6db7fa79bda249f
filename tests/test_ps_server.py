"""PS server tier tests.

Harness mirrors the reference's fake-distributed single-node pattern
(reference: tests/meta_test.py:26-84 — launch scheduler+server
subprocesses, run a multi-worker workload against them in one process).
Here: start the native KV server as a subprocess, drive it with N
PSSession workers on threads, assert summed push_pull semantics.
"""

import os
import socket
import struct
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from byteps_tpu.server.client import PSSession, _ServerConn, CMD_SHUTDOWN


from testutil import cpu_env, free_port


@pytest.fixture
def ps_server():
    """Yields (port, num_workers) with a live server; kills it after."""
    made = []

    def start(num_workers=2, schedule=False, async_mode=False,
              extra_env=None, capture_stderr=False):
        """Returns the port; with capture_stderr=True returns (port, proc)
        so the test can read the server's stderr (debug tracing).

        free_port() is bind-then-close (TOCTOU): under parallel test
        workers another process can claim the port before the server
        binds it, killing the server at startup — retry with a fresh
        port."""
        last = None
        for _ in range(3):
            try:
                return _start_once(num_workers, schedule, async_mode,
                                   extra_env, capture_stderr)
            except RuntimeError as e:   # died at startup (bind race)
                last = e
        raise last

    def _start_once(num_workers, schedule, async_mode, extra_env,
                    capture_stderr):
        port = free_port()
        env = cpu_env({
            # serve() binds scheduler_port + 1 + server_id
            "DMLC_PS_ROOT_PORT": str(port - 1),
            "DMLC_NUM_WORKER": str(num_workers),
            "BYTEPS_SERVER_ENGINE_THREAD": "2",
            "BYTEPS_SERVER_ENABLE_SCHEDULE": "1" if schedule else "0",
            "BYTEPS_ENABLE_ASYNC": "1" if async_mode else "0",
            "JAX_PLATFORMS": "cpu",
            **(extra_env or {}),
        })
        if env.get("BYTEPS_TPU_TSAN") == "1":
            # Make any detected race fatal: the server dies mid-test and the
            # functional assertions fail, so TSAN findings fail CI.
            env["TSAN_OPTIONS"] = "halt_on_error=1"
        proc = subprocess.Popen(
            [sys.executable, "-m", "byteps_tpu.server"], env=env,
            stdout=subprocess.DEVNULL,
            stderr=subprocess.PIPE if capture_stderr else subprocess.DEVNULL,
            text=capture_stderr or None)
        made.append(proc)
        # wait for the listening socket
        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                socket.create_connection(("127.0.0.1", port), 0.5).close()
                return (port, proc) if capture_stderr else port
            except OSError:
                if proc.poll() is not None:
                    raise RuntimeError(
                        f"server died rc={proc.returncode}")
                time.sleep(0.1)
        raise TimeoutError("PS server did not come up")

    yield start
    for p in made:
        p.kill()
        p.wait()


def _session(port, wid, n=1):
    return PSSession(["127.0.0.1"], [port], worker_id=wid, num_servers=n)


def test_push_pull_sums_across_workers(ps_server):
    port = ps_server(num_workers=2)
    a = np.arange(100, dtype=np.float32)
    b = 10 * np.arange(100, dtype=np.float32)
    out = {}

    def worker(wid, data):
        s = _session(port, wid)
        out[wid] = s.push_pull(7, data)
        s.close()

    ts = [threading.Thread(target=worker, args=(0, a)),
          threading.Thread(target=worker, args=(1, b))]
    [t.start() for t in ts]
    [t.join(timeout=60) for t in ts]
    np.testing.assert_allclose(out[0], a + b)
    np.testing.assert_allclose(out[1], a + b)


def test_multiple_rounds_and_keys(ps_server):
    port = ps_server(num_workers=2)
    results = {0: [], 1: []}

    def worker(wid):
        s = _session(port, wid)
        for step in range(3):
            for key in (1, 2):
                x = np.full(50, float(wid + 1 + step), np.float32)
                results[wid].append((step, key, s.push_pull(key, x)))
        s.close()

    ts = [threading.Thread(target=worker, args=(w,)) for w in (0, 1)]
    [t.start() for t in ts]
    [t.join(timeout=60) for t in ts]
    for wid in (0, 1):
        for step, key, got in results[wid]:
            expect = np.full(50, (1 + step) + (2 + step), np.float32)
            np.testing.assert_allclose(got, expect,
                                       err_msg=f"wid={wid} step={step}")


def test_barrier(ps_server):
    port = ps_server(num_workers=2)
    order = []

    def worker(wid, delay):
        s = _session(port, wid)
        time.sleep(delay)
        order.append(("before", wid, time.monotonic()))
        s.barrier()
        order.append(("after", wid, time.monotonic()))
        s.close()

    ts = [threading.Thread(target=worker, args=(0, 0.0)),
          threading.Thread(target=worker, args=(1, 0.5))]
    [t.start() for t in ts]
    [t.join(timeout=60) for t in ts]
    afters = [t for tag, _, t in order if tag == "after"]
    befores = [t for tag, _, t in order if tag == "before"]
    assert max(befores) <= min(afters) + 1e-3  # nobody crossed early


def test_async_mode_accumulates(ps_server):
    """Async PS mode: pushes apply immediately, pull returns current store
    (reference: server.cc:319-323, BYTEPS_ENABLE_ASYNC)."""
    port = ps_server(num_workers=1, async_mode=True)
    s = _session(port, 0)
    x = np.ones(10, np.float32)
    r1 = s.push_pull(3, x)
    r2 = s.push_pull(3, x)
    np.testing.assert_allclose(r1, x)
    np.testing.assert_allclose(r2, 2 * x)  # store kept growing
    s.close()


def test_schedule_mode_correctness(ps_server):
    """Priority scheduling must not change results."""
    port = ps_server(num_workers=2, schedule=True)
    out = {}

    def worker(wid):
        s = _session(port, wid)
        acc = []
        for key in range(8):
            x = np.full(1000, float(key + wid), np.float32)
            acc.append(s.push_pull(key, x))
        out[wid] = acc
        s.close()

    ts = [threading.Thread(target=worker, args=(w,)) for w in (0, 1)]
    [t.start() for t in ts]
    [t.join(timeout=60) for t in ts]
    for key in range(8):
        np.testing.assert_allclose(out[0][key],
                                   np.full(1000, 2.0 * key + 1, np.float32))


def test_dedup_within_round(ps_server):
    """A duplicate push from the same worker in one round is ignored
    (reference: server.cc:150-177 seen_sender dedup)."""
    port = ps_server(num_workers=2)
    a = np.ones(10, np.float32)

    def w0():
        s = _session(port, 0)
        s.conns[0].request(2, 9, a.tobytes(), worker_id=0)   # PUSH
        s.conns[0].request(2, 9, a.tobytes(), worker_id=0)   # dup PUSH
        out["w0"] = np.frombuffer(
            s.conns[0].request(3, 9, worker_id=0), np.float32)  # PULL
        s.close()

    def w1():
        s = _session(port, 1)
        time.sleep(0.3)
        s.conns[0].request(1, 9, struct.pack("<Q", a.nbytes), worker_id=1)
        s.conns[0].request(2, 9, a.tobytes(), worker_id=1)
        out["w1"] = np.frombuffer(
            s.conns[0].request(3, 9, worker_id=1), np.float32)
        s.close()

    out = {}
    # worker 0 INITs first so the buffer exists
    s = _session(port, 0)
    s.conns[0].request(1, 9, struct.pack("<Q", a.nbytes), worker_id=0)
    s.close()
    ts = [threading.Thread(target=w0), threading.Thread(target=w1)]
    [t.start() for t in ts]
    [t.join(timeout=60) for t in ts]
    np.testing.assert_allclose(out["w0"], 2 * a)  # not 3a
    np.testing.assert_allclose(out["w1"], 2 * a)


def test_size_change_from_seen_worker_not_dropped_as_dup(ps_server):
    """A worker already in `seen` that re-pushes the SAME key with a NEW
    payload size (re-declared tensor mid-round) must trigger the
    size-change merge reset, not be acked-and-dropped by the dedup
    (ADVICE round 5: the dedup ran before the size check, so after the
    reset cleared `seen` the round stayed one push short forever and
    every pull hung)."""
    port = ps_server(num_workers=2)
    key = 13
    a = np.ones(16, np.float32)                  # original size
    b = np.full(32, 2.0, np.float32)             # re-declared size

    s0 = _session(port, 0)
    s1 = _session(port, 1)
    # Worker 0 joins the round at the original size: seen = {0}.
    s0.conns[0].request(1, key, struct.pack("<QI", a.nbytes, 0), worker_id=0)
    s0.conns[0].request(2, key, a.tobytes(), worker_id=0)
    # Worker 0 re-pushes the key at the NEW size with no intervening INIT
    # (a re-INIT's own size check would mask the bug by clearing `seen`
    # first, HandleInit).  Must reset the merge (store=b, seen={0}), NOT
    # vanish as a dup: pre-fix, this ack-and-drop left worker 0 out of the
    # restarted merge forever.
    s0.conns[0].request(2, key, b.tobytes(), worker_id=0)
    # Worker 1 completes the round at the new size (its INIT sees the
    # already-resized store, so worker 0's contribution survives).
    s1.conns[0].request(1, key, struct.pack("<QI", b.nbytes, 0), worker_id=1)
    s1.conns[0].request(2, key, b.tobytes(), worker_id=1)
    # Both pulls must serve the 2-way size-B merge (pre-fix: hangs —
    # the 30s timeout turns the wedge into a loud failure).
    for s, wid in ((s0, 0), (s1, 1)):
        got = np.frombuffer(
            s.conns[0].request(3, key, worker_id=wid, timeout=30.0),
            np.float32)
        np.testing.assert_array_equal(got, 2 * b)
    s0.close()
    s1.close()


def test_pull_with_impossible_round_rejected(ps_server):
    """The pull round rides the low 15 bits of the u16 flags (bit 15 is
    the trace marker); the server asserts the sequential-use invariant
    (pull round == completed_round or completed_round - 1) instead of
    silently pending on an aliased round 32,768 stale
    (core/server.cc HandlePull)."""
    port = ps_server(num_workers=1)
    a = np.ones(8, np.float32)
    s = _session(port, 0)
    s.conns[0].request(1, 5, struct.pack("<Q", a.nbytes), worker_id=0)
    s.conns[0].request(2, 5, a.tobytes(), worker_id=0)      # push round 0
    got = np.frombuffer(
        s.conns[0].request(3, 5, worker_id=0, flags=0), np.float32)
    np.testing.assert_allclose(got, a)
    with pytest.raises(RuntimeError, match="server error"):
        s.conns[0].request(3, 5, worker_id=0, flags=1234)
    s.close()


def test_shutdown_terminates_server(ps_server):
    """SHUTDOWN must stop the server even with another idle connection open
    (readers blocked in recv are unblocked by the half-close)."""
    port = ps_server(num_workers=2)
    idle = _session(port, 1)       # stays connected, idle
    s = _session(port, 0)
    s.shutdown_servers()
    # the fixture's Popen object is the last one created
    import tests.test_ps_server  # noqa: F401  (self-import for clarity)
    # wait for exit via connect failures
    deadline = time.time() + 15
    down = False
    while time.time() < deadline:
        try:
            socket.create_connection(("127.0.0.1", port), 0.3).close()
            time.sleep(0.2)
        except OSError:
            down = True
            break
    idle.close()
    s.close()
    assert down, "server still accepting after SHUTDOWN"


def test_large_tensor_partitioned_across_servers(ps_server):
    """A >16MB tensor must be split into multiple partition keys spread over
    distinct servers, and the summed result must match bit-for-bit
    (reference: operations.cc:140-180 partitioning, global.cc:643-692
    key->server spreading)."""
    port_a = ps_server(num_workers=2)
    port_b = ps_server(num_workers=2)
    n = (17 * 1024 * 1024) // 4  # 17MB of f32
    rng = np.random.RandomState(0)
    a = rng.randn(n).astype(np.float32)
    b = rng.randn(n).astype(np.float32)
    out = {}

    def worker(wid, data):
        s = PSSession(["127.0.0.1"] * 2, [port_a, port_b], worker_id=wid,
                      num_servers=2)
        plan = s._plan(11, data.nbytes)
        # >=5 partitions at the default 4MB bound, on >=2 distinct servers
        assert len(plan) >= 5
        servers_used = {srv for (_, _, _, srv) in plan}
        assert len(servers_used) >= 2, "partitions all landed on one server"
        keys = [pkey for (pkey, _, _, _) in plan]
        assert len(set(keys)) == len(keys)
        assert all(k >> 16 == 11 for k in keys)
        out[wid] = s.push_pull(11, data)
        s.close()

    ts = [threading.Thread(target=worker, args=(0, a)),
          threading.Thread(target=worker, args=(1, b))]
    [t.start() for t in ts]
    [t.join(timeout=120) for t in ts]
    expect = a + b  # same add order as server (COPY_FIRST then SUM_RECV)
    np.testing.assert_array_equal(out[0], expect)
    np.testing.assert_array_equal(out[1], expect)


def test_wire_conns_spread_partitions_over_lanes(ps_server):
    """With wire_conns=2, a multi-partition tensor's data must spread over
    both lanes of each server — lanes are picked at DISPATCH time by byte
    credit (least-outstanding-bytes, ties to fewest sends), so after a few
    rounds every lane must have carried traffic, for EVERY placement hash
    (plan-time assignment no longer exists to degenerate)."""
    port = ps_server(num_workers=1)
    for hash_fn in ("naive", "djb2"):
        s = PSSession(["127.0.0.1"], [port], worker_id=0, num_servers=1,
                      hash_fn=hash_fn, partition_bytes=65536, wire_conns=2)
        data = np.arange(8 * 65536 // 4, dtype=np.float32)
        plan = s._plan(3, data.nbytes)
        assert len(plan) == 8
        assert all(srv == 0 for (_, _, _, srv) in plan)
        for _ in range(3):
            np.testing.assert_array_equal(s.push_pull(3, data), data)
        lanes = s.transport_stats()["lanes"]
        assert len(lanes) == 2
        assert all(l["sends"] > 0 for l in lanes), \
            f"idle lane under hash_fn={hash_fn}: {lanes}"
        assert all(l["outstanding_bytes"] == 0 for l in lanes), \
            f"leaked lane credit: {lanes}"
        s.close()


def test_priority_scheduling_with_credit(ps_server):
    """With a constrained credit, queued partitions must dispatch in
    (priority desc, key asc) order: a high-priority tensor enqueued after a
    low-priority one still pushes first (reference control law:
    scheduled_queue.cc:26-46,136-139)."""
    port = ps_server(num_workers=1)
    s = PSSession(["127.0.0.1"], [port], worker_id=0, num_servers=1,
                  partition_bytes=1024, scheduling_credit=1)
    s.record_push_order = True
    s.pause_dispatch()
    a = np.ones(1024, np.float32)   # 4096 bytes -> 4 partitions
    b = np.ones(512, np.float32)    # 2048 bytes -> 2 partitions
    ha = s.push_pull_async(1, a, priority=0)   # low, enqueued first
    hb = s.push_pull_async(2, b, priority=10)  # high, enqueued second
    s.resume_dispatch()
    ra, rb = ha.wait(), hb.wait()
    np.testing.assert_array_equal(ra, a)
    np.testing.assert_array_equal(rb, b)
    order = list(s.push_order)
    expect_b = [(2 << 16) | i for i in range(2)]
    expect_a = [(1 << 16) | i for i in range(4)]
    assert order == expect_b + expect_a, order
    s.close()


def test_concurrent_partition_pipelining(ps_server):
    """Without credit limits, many partitions are outstanding at once on a
    multiplexed connection; results stay correct under 2 workers x 3
    tensors x several rounds."""
    port = ps_server(num_workers=2)
    results = {0: [], 1: []}

    def worker(wid):
        s = PSSession(["127.0.0.1"], [port], worker_id=wid, num_servers=1,
                      partition_bytes=256)
        for step in range(3):
            hs = [s.push_pull_async(k, np.full(512, float(wid + step + k),
                                               np.float32), priority=-k)
                  for k in range(3)]
            results[wid].append([h.wait() for h in hs])
        s.close()

    ts = [threading.Thread(target=worker, args=(w,)) for w in (0, 1)]
    [t.start() for t in ts]
    [t.join(timeout=120) for t in ts]
    for wid in (0, 1):
        for step in range(3):
            for k in range(3):
                expect = np.full(512, (0 + step + k) + (1 + step + k),
                                 np.float32)
                np.testing.assert_array_equal(results[wid][step][k], expect)


def test_reconnect_reseeds_round_from_server(ps_server):
    """A worker that reconnects (crash restart / elastic rejoin) must seed
    its round counters from the server's completed_round (returned by INIT)
    — a fresh client starting at round 0 would otherwise be served the
    previous round's stale buffer immediately."""
    port = ps_server(num_workers=1)
    s1 = _session(port, 0)
    for step in range(3):
        s1.push_pull(5, np.full(16, float(step + 1), np.float32))
    s1.close()
    # Reconnect: new session, same key, new value. Must get the NEW sum,
    # not the stale round-3 buffer (which holds 3.0s).
    s2 = _session(port, 0)
    got = s2.push_pull(5, np.full(16, 42.0, np.float32))
    np.testing.assert_array_equal(got, np.full(16, 42.0, np.float32))
    s2.close()


def test_server_crash_propagates_error_to_waiters(ps_server):
    """A server death mid-training must fail the worker loudly (pending
    futures resolve with ConnectionError via _fail_pending), not hang it —
    the failure-detection contract a training job needs to restart."""
    port = ps_server(num_workers=1)
    s = _session(port, 0)
    x = np.ones(64, np.float32)
    np.testing.assert_allclose(s.push_pull(21, x), x)  # healthy round
    # Kill the server out from under the session.
    conn = _ServerConn("127.0.0.1", port)
    conn.send(CMD_SHUTDOWN, worker_id=0)
    deadline = time.time() + 15
    while time.time() < deadline:
        try:
            socket.create_connection(("127.0.0.1", port), 0.3).close()
            time.sleep(0.1)
        except OSError:
            break
    with pytest.raises((ConnectionError, TimeoutError, RuntimeError)):
        # either the INIT/push send fails or the pull future is failed
        s.push_pull(21, x)
    s.close()
    conn.close()


def test_worker_restart_mid_training_against_live_servers(ps_server):
    """Elastic restart in context: two workers run a gradient-descent loop
    through the live server; worker 1 crashes between rounds and a
    replacement session rejoins.  The reseed-from-INIT path
    (client.py _stage_parts round seeding) must land the restarted worker in
    the server's current round — training continues with correct sums, no
    stale-round pull (reference demo:
    example/pytorch/elastic_benchmark_byteps.py:124-133)."""
    port = ps_server(num_workers=2)
    key = 11
    n = 64
    w = {0: np.full(n, 10.0, np.float32), 1: np.full(n, 10.0, np.float32)}
    barrier = threading.Barrier(2)
    sums = {0: [], 1: []}

    def train_rounds(sess, wid, grads):
        for g in grads:
            got = sess.push_pull(key, np.full(n, g, np.float32))
            sums[wid].append(got[0])
            w[wid] = w[wid] - 0.1 * got / 2.0  # mean of worker grads
            barrier.wait(timeout=60)

    def worker0():
        s = _session(port, 0)
        train_rounds(s, 0, [1.0, 2.0])      # rounds 0-1 with original peer
        train_rounds(s, 0, [3.0, 4.0])      # rounds 2-3 with restarted peer
        s.close()

    def worker1():
        s = _session(port, 1)
        train_rounds(s, 1, [1.0, 2.0])
        s.close()                            # "crash" between rounds
        s2 = _session(port, 1)               # replacement joins live server
        train_rounds(s2, 1, [3.0, 4.0])
        s2.close()

    ts = [threading.Thread(target=worker0), threading.Thread(target=worker1)]
    [t.start() for t in ts]
    [t.join(timeout=120) for t in ts]
    assert not any(t.is_alive() for t in ts)
    # Each round's sum is grad_w0 + grad_w1 = 2*g; a stale-round pull after
    # the restart would have returned round 1's 4.0 for round 2.
    np.testing.assert_allclose(sums[0], [2.0, 4.0, 6.0, 8.0])
    np.testing.assert_allclose(sums[1], [2.0, 4.0, 6.0, 8.0])
    # Both replicas stayed in lockstep through the restart.
    np.testing.assert_allclose(w[0], w[1])


def test_oversize_frame_drops_connection_not_server(ps_server):
    """A wire frame whose length field exceeds BYTEPS_SERVER_MAX_MSG_BYTES
    (corrupted client, stray non-protocol connection) must cost only that
    connection — a naive `vector(h.len)` would bad_alloc and take down the
    whole PS tier.  The server must keep serving existing and new
    sessions afterwards."""
    from byteps_tpu.server.client import _REQ

    port = ps_server(num_workers=1)
    s = _session(port, 0)
    x = np.arange(32, dtype=np.float32)
    np.testing.assert_array_equal(s.push_pull(7, x), x)  # healthy round

    # Hand-craft a header claiming a 1 TB payload on a raw socket.
    rogue = socket.create_connection(("127.0.0.1", port), 5)
    rogue.sendall(_REQ.pack(2, 0, 0, 1, 0, 99, 1 << 40))
    # The server must close THIS connection (read returns EOF)...
    rogue.settimeout(10)
    assert rogue.recv(1) == b"", "oversize frame was not rejected"
    rogue.close()

    # A connect-and-send-garbage LOOP must not leak fds either (each
    # rejected conn's fd is reclaimed on reader exit because nothing
    # referenced it) — 50 attempts would show up quickly against a
    # lowered fd budget; here we just assert the tier stays healthy.
    for _ in range(50):
        r = socket.create_connection(("127.0.0.1", port), 5)
        r.sendall(_REQ.pack(2, 0, 0, 1, 0, 99, 1 << 40))
        r.settimeout(10)
        assert r.recv(1) == b""
        r.close()

    # A compressed push whose header CLAIMS a 16GB decompressed size (a
    # 9-byte payload: comp u8 + n u32 + 4 filler, n=0xFFFFFFFF) must get
    # an error response — not a bad_alloc in the engine thread.
    bad = struct.pack("<BI", 1, 0xFFFFFFFF) + b"\0\0\0\0"  # onebit, huge n
    crafty = socket.create_connection(("127.0.0.1", port), 5)
    crafty.sendall(_REQ.pack(2, 2, 0, 7, 0, 99, len(bad)) + bad)
    crafty.settimeout(10)
    resp = b""
    while len(resp) < 21:     # RespHeader: status u8, req_id u32, 2x u64
        chunk = crafty.recv(21 - len(resp))
        assert chunk, "no response to oversize-claim compressed push"
        resp += chunk
    status, req_id, _, _ = struct.unpack("<BIQQ", resp)
    assert status != 0 and req_id == 7, "bogus decompress size not rejected"
    crafty.close()

    # ...while the live session and a brand-new one keep working.
    np.testing.assert_array_equal(s.push_pull(7, 2 * x), 2 * x)
    s2 = _session(port, 0)
    np.testing.assert_array_equal(s2.push_pull(8, x), x)
    s.close()
    s2.close()


def test_api_push_pull_via_ps_mode(ps_server):
    """BYTEPS_TPU_PS_MODE=1 routes bps.push_pull through the server tier,
    partitioned and priority-scheduled, transparently to the API user."""
    port = ps_server(num_workers=1)
    code = """
import numpy as np, jax.numpy as jnp
import byteps_tpu as bps
bps.init()
x = jnp.arange(100000, dtype=jnp.float32)
out = bps.push_pull(x, name="g", average=False)
np.testing.assert_array_equal(np.asarray(out),
                              np.arange(100000, dtype=np.float32))
h = bps.push_pull_async(2 * x, name="g2", average=False)
assert bps.poll(h) in (True, False)
out2 = bps.synchronize(h)
np.testing.assert_array_equal(np.asarray(out2),
                              2 * np.arange(100000, dtype=np.float32))
bps.shutdown()
print("PS_API_OK")
"""
    env = cpu_env({
        "BYTEPS_TPU_PS_MODE": "1",
        "DMLC_NUM_WORKER": "1",
        "DMLC_NUM_SERVER": "1",
        "DMLC_PS_ROOT_PORT": str(port - 1),
        # Small partitions so even this test exercises the partitioned path.
        "BYTEPS_PARTITION_BYTES": "65536",
        "BYTEPS_SCHEDULING_CREDIT": "4",
    })
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "PS_API_OK" in proc.stdout


def test_push_pull_tree_preserves_wire_compression(ps_server):
    """In PS mode, a tree leaf whose name has a registered wire compressor
    must NOT be folded into the batched key (that would silently bypass
    the user's compression): it rides its own named push_pull through the
    compressed wire — the result is the onebit requantization, not the
    exact value — while unregistered leaves batch exactly."""
    port = ps_server(num_workers=1)
    code = """
import numpy as np, jax.numpy as jnp
import byteps_tpu as bps
from byteps_tpu.server import wire
bps.init()
bps.register_compressor("comp.g", {"compressor": "onebit"})
g = jnp.asarray(np.linspace(-2.0, 3.0, 4096, dtype=np.float32))
tree = {"comp.g": g, "plain.h": jnp.full((64,), 7.0, jnp.float32)}
out = bps.push_pull_tree(tree, average=False, leaf_names=sorted(tree))
# compressed leaf: one-worker onebit round-trip = sign * mean|g| (twice:
# worker push + server bidirectional requantize keep the same values)
wc = wire.WireCompressor({"compressor": "onebit"})
want = wire.decode(wc.encode(0, np.asarray(g)), g.size)
want = wire.decode(wc.encode(0, want), want.size)
np.testing.assert_allclose(np.asarray(out["comp.g"]), want, rtol=1e-6)
assert not np.allclose(np.asarray(out["comp.g"]), np.asarray(g))
# plain leaf: exact through the batched path
np.testing.assert_array_equal(np.asarray(out["plain.h"]),
                              np.full((64,), 7.0, np.float32))
bps.shutdown()
print("TREE_COMP_OK")
"""
    env = cpu_env({
        "BYTEPS_TPU_PS_MODE": "1",
        "DMLC_NUM_WORKER": "1",
        "DMLC_NUM_SERVER": "1",
        "DMLC_PS_ROOT_PORT": str(port - 1),
        "BYTEPS_MIN_COMPRESS_BYTES": "0",
    })
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "TREE_COMP_OK" in proc.stdout


def test_server_debug_value_tracing(ps_server):
    """BYTEPS_SERVER_DEBUG logs push merges and round publishes with the
    f32 sum of the buffer; BYTEPS_SERVER_DEBUG_KEY filters to one key
    (reference: BYTEPS_SERVER_DEBUG(_KEY), server.cc:124-201)."""
    port, proc = ps_server(
        num_workers=1, capture_stderr=True,
        extra_env={"BYTEPS_SERVER_DEBUG": "1",
                   "BYTEPS_SERVER_DEBUG_KEY": str(5 << 16)})
    s = _session(port, 0)
    # The session encodes wire keys as (declared_key << 16) | part.
    s.push_pull(5, np.full(8, 2.0, np.float32))   # traced key
    s.push_pull(9, np.ones(8, np.float32))        # filtered out
    s.close()
    proc.terminate()
    err = proc.communicate(timeout=30)[1]
    assert "push_recv" in err and "all_recv" in err, err[-2000:]
    assert f"key={5 << 16}" in err
    assert "f32_sum=16" in err          # 8 elements x 2.0
    assert f"key={9 << 16}" not in err  # DEBUG_KEY filter applies
    # push and publish of the same round carry the same round number
    assert "push_recv key=327680 worker=0 round=0" in err
    assert "all_recv key=327680 worker=0 round=0" in err
