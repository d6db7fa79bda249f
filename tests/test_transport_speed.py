"""Raw-speed transport overhaul tests.

Covers the receive-path and lane-scheduling rework: the size-classed
pooled-buffer receive ring (reuse + no-aliasing under concurrent pulls),
byte-credit lane picking (least-outstanding-bytes wins, unit-tested on
stubbed conns), the AF_UNIX fast path (bit-identical results vs TCP for
raw, onebit, and fusion-group traffic against the REAL native server),
the server's scatter-receive merge path (identical results for declared
and undeclared-key orderings, proven against live stats), and the
BYTEPS_TPU_SOCK_BUF_KB socket-tuning knob.
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

from byteps_tpu.server.client import (
    HANDOFF_DEPTH, PSSession, _RecvBufPool, _REQ, _RESP,
    CMD_INIT, CMD_PUSH, CMD_PULL,
)

from testutil import cpu_env, free_port


# ---------------------------------------------------------------------------
# harness (same shape as tests/test_transport_fault.py)
# ---------------------------------------------------------------------------
@pytest.fixture
def ps_server():
    """`start(...) -> port` with a live native server; killed after."""
    made = []

    def start(num_workers=1, extra_env=None, port=None):
        last = None
        for _ in range(3):
            try:
                return _start_once(num_workers, extra_env, port)
            except RuntimeError as e:
                last = e
                if port is not None:
                    raise
        raise last

    def _start_once(num_workers, extra_env, port):
        port = port or free_port()
        env = cpu_env({
            "DMLC_PS_ROOT_PORT": str(port - 1),
            "DMLC_NUM_WORKER": str(num_workers),
            "BYTEPS_SERVER_ENGINE_THREAD": "2",
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

    start.procs = made
    yield start
    for p in made:
        p.kill()
        p.wait()


def _session(port, **kw):
    return PSSession(["127.0.0.1"], [port], worker_id=0, num_servers=1, **kw)


def _transports(sess):
    return {c.transport for pool in sess._data_conns for c in pool}


# ---------------------------------------------------------------------------
# receive buffer pool
# ---------------------------------------------------------------------------
def test_recv_pool_reuse_and_no_aliasing():
    pool = _RecvBufPool()
    a = pool.acquire(1000)
    b = pool.acquire(1000)
    # Two concurrent checkouts of the same class never share storage.
    assert a._buf is not b._buf
    a.mv[:4] = b"aaaa"
    b.mv[:4] = b"bbbb"
    assert bytes(a.mv[:4]) == b"aaaa"
    assert len(a) == 1000
    buf_a = a._buf
    a.release()
    # Same-class re-acquire reuses the released buffer (a hit) ...
    c = pool.acquire(500)
    assert c._buf is buf_a
    hits, misses, _held = pool.stats()
    assert hits == 1 and misses == 2
    # ... and release is idempotent (error paths call it defensively).
    c.release()
    c.release()
    assert pool.stats()[2] == 1   # only c's buffer back; b still out
    b.release()
    assert pool.stats()[2] == 2
    # Oversize payloads fall back to a one-shot allocation, unpooled.
    big = pool.acquire((1 << 24) + 1)
    assert len(big) == (1 << 24) + 1
    assert big._cls is None
    big.release()


def test_recv_pool_bounded_retention():
    pool = _RecvBufPool()
    bufs = [pool.acquire(8192) for _ in range(2 * _RecvBufPool.PER_CLASS)]
    for b in bufs:
        b.release()
    assert pool.stats()[2] == _RecvBufPool.PER_CLASS


def test_pool_hits_and_exact_results_under_concurrent_compressed_pulls(
        ps_server):
    """Bidirectional (onebit) pulls come back re-compressed at a different
    length than the sink, so they ride pooled buffers; several keys in
    flight at once must (a) produce exactly the single-worker reference
    values and (b) actually recycle buffers (pool hits > 0) without any
    cross-key corruption — the no-aliasing contract under load."""
    from byteps_tpu.server import wire

    port = ps_server()
    s = _session(port, min_compress_bytes=0)
    try:
        n = 16384
        rng = np.random.RandomState(11)
        data = {k: rng.randn(n).astype(np.float32) for k in range(20, 24)}
        expect = {}
        for k, x in data.items():
            s.register_compressor(k, {"compressor": "onebit"})
            # Single worker: the server's merged store IS the decoded
            # worker blob, and its onebit re-encode round-trips it
            # exactly (same signs, same scale).
            wc = wire.WireCompressor({"compressor": "onebit"})
            expect[k] = wire.decode(wc.encode(k, x), n)
        for rnd in range(4):
            handles = [(k, s.push_pull_async(k, x))
                       for k, x in data.items()]
            for k, h in handles:
                np.testing.assert_array_equal(h.wait(30.0), expect[k],
                                              err_msg=f"key {k} rnd {rnd}")
        st = s.transport_stats()
        assert st["pool_hits"] > 0, st
        assert st["lane_outstanding_bytes"] == 0, st
    finally:
        s.close()


# ---------------------------------------------------------------------------
# byte-credit lane scheduling
# ---------------------------------------------------------------------------
class _StubConn:
    def __init__(self, outstanding, sends=0, state="up"):
        self.outstanding_bytes = outstanding
        self.lane_sends = sends
        self._state = state

    def state(self):
        return self._state

    def has_room(self):
        return True


def test_credit_scheduler_picks_least_loaded_lane():
    a, b, c = _StubConn(100), _StubConn(5), _StubConn(50)
    assert PSSession._pick_lane_from([a, b, c]) is b
    # Reconnecting lanes are skipped while any lane is up.
    down = _StubConn(0, state="reconnecting")
    assert PSSession._pick_lane_from([a, down, c]) is c
    # Ties break to fewest lifetime sends, so idle lanes rotate.
    d, e = _StubConn(0, sends=9), _StubConn(0, sends=2)
    assert PSSession._pick_lane_from([d, e]) is e
    # Single-lane pools short-circuit.
    assert PSSession._pick_lane_from([a]) is a
    # With every lane down, the least-loaded one still gets the send
    # (it raises/parks there rather than deadlocking the dispatcher).
    f = _StubConn(3, state="reconnecting")
    g = _StubConn(1, state="closed")
    assert PSSession._pick_lane_from([f, g]) is g


def test_lane_credit_settles_to_zero_and_spreads(ps_server):
    port = ps_server()
    s = _session(port, partition_bytes=65536, wire_conns=3)
    try:
        x = np.arange(9 * 65536 // 4, dtype=np.float32)   # 9 partitions
        for _ in range(3):
            np.testing.assert_array_equal(s.push_pull(6, x), x)
        lanes = s.transport_stats()["lanes"]
        assert len(lanes) == 3
        assert all(l["outstanding_bytes"] == 0 for l in lanes), lanes
        assert sum(l["sends"] for l in lanes) >= 27
        assert all(l["sends"] > 0 for l in lanes), lanes
    finally:
        s.close()


# ---------------------------------------------------------------------------
# a sender a lane: the dispatcher decides, the lane's sender writes
# ---------------------------------------------------------------------------
class _GatedLane:
    """Stands in for a lane's socket: a push's `send` waits at a gate
    the test opens a frame at a time, and notes what waited behind it."""

    def __init__(self, conn):
        self.conn, self.real = conn, conn.send
        self.gate = threading.Semaphore(0)
        self.pushes = []            # keys, in the order the sender took them
        self.most_waiting = 0
        conn.send = self

    def __call__(self, cmd, key=0, *args, **kw):
        if cmd == CMD_PUSH:
            self.pushes.append(key)
            self.most_waiting = max(self.most_waiting,
                                    len(self.conn._waiting))
            self.gate.acquire()
        return self.real(cmd, key, *args, **kw)

    def open(self, frames=1000):
        for _ in range(frames):
            self.gate.release()


def _until(cond, what, seconds=10.0):
    deadline = time.time() + seconds
    while not cond():
        assert time.time() < deadline, what
        time.sleep(0.005)


def test_hand_over_is_shallow_and_keeps_priority(ps_server):
    """On lanes whose socket the test holds shut: the dispatcher hands a
    lane one frame behind the one being sent and pops no more, and a
    high-priority partition staged while every lane is full is the next
    one handed over when a place comes free."""
    s = _session(ps_server(), partition_bytes=65536, wire_conns=2)
    lanes = [_GatedLane(c) for c in s._data_conns[0]]
    try:
        s.record_push_order = True
        low = np.arange(10 * 65536 // 4, dtype=np.float32)  # 10 partitions
        h_low = s.push_pull_async(3, low, priority=0)
        held = 1 + HANDOFF_DEPTH
        _until(lambda: all(l.conn.frames_held == held for l in lanes),
               "the lanes never filled")
        time.sleep(0.1)         # a dispatcher that went on would show now
        assert len(s.push_order) == 2 * held
        assert s._queue.pending() == 10 - 2 * held
        assert [len(l.conn._waiting) for l in lanes] == [HANDOFF_DEPTH] * 2
        assert not any(l.conn.has_room() for l in lanes)
        urgent = np.full(100, 7.0, np.float32)              # 1 partition
        h_urgent = s.push_pull_async(4, urgent, priority=9)
        _until(lambda: s._queue.pending() == 11 - 2 * held,
               "the urgent partition never reached the queue")
        assert len(s.push_order) == 2 * held    # full lanes: nothing pops
        lanes[1].open(1)
        _until(lambda: len(s.push_order) == 2 * held + 1,
               "a freed place was not filled")
        assert s.push_order[-1] >> 16 == 4
        assert lanes[1].conn._waiting[-1][2] == s.push_order[-1]
        for l in lanes:
            l.open()
        np.testing.assert_array_equal(h_urgent.wait(30.0), urgent)
        np.testing.assert_array_equal(h_low.wait(30.0), low)
        assert max(l.most_waiting for l in lanes) <= HANDOFF_DEPTH
        assert sorted(k for l in lanes for k in l.pushes) == sorted(
            s.push_order)
        st = s.transport_stats()
        assert st["push_handoffs"] == 11
        assert st["lane_outstanding_bytes"] == 0, st
    finally:
        for l in lanes:
            l.open()
        s.close()


def test_a_pull_request_goes_ahead_of_the_pushes_waiting(ps_server):
    """A frame with no payload (a pull's request) handed to a lane is
    sent before the pushes waiting there, and `hand_over` returns at
    once whatever the socket is doing."""
    s = _session(ps_server(), partition_bytes=65536, wire_conns=1)
    lane = _GatedLane(s._data_conns[0][0])
    order = []
    real = lane.real

    def noting(cmd, key=0, *args, **kw):
        order.append((cmd, key))
        return real(cmd, key, *args, **kw)

    lane.real = noting
    try:
        x = np.arange(3 * 65536 // 4, dtype=np.float32)     # 3 partitions
        h = s.push_pull_async(5, x)
        _until(lambda: lane.conn.frames_held == 1 + HANDOFF_DEPTH,
               "the lane never filled")
        errors = []
        t0 = time.time()
        lane.conn.hand_over(errors.append, CMD_PULL, 12345, worker_id=0,
                            callback=lambda data, err: errors.append(err))
        assert time.time() - t0 < 0.5
        lane.open()
        np.testing.assert_array_equal(h.wait(30.0), x)
        # the request went out after the push the gate held, before the
        # push that waited behind it
        frames = [f for f in order if f[0] in (CMD_PUSH, CMD_PULL)]
        assert frames[0][0] == CMD_PUSH
        assert frames[1] == (CMD_PULL, 12345), frames
        assert sum(c == CMD_PUSH for c, _ in frames) == 3
    finally:
        lane.open()
        s.close()


def test_every_push_leaves_through_its_lanes_sender(ps_server):
    """A round over three lanes against a live server: every push was
    sent by a lane's sender, every lane carried some, and the lanes'
    byte credit settles."""
    s = _session(ps_server(), partition_bytes=65536, wire_conns=3)
    try:
        senders = {c._send_thread for c in s._data_conns[0]}
        assert len(senders) == 3 and all(
            t.is_alive() and t.name == "bps-ps-send" for t in senders)
        x = np.arange(12 * 65536 // 4, dtype=np.float32)   # 12 partitions
        rounds = 3
        for _ in range(rounds):
            np.testing.assert_array_equal(s.push_pull(8, x), x)
        st = s.transport_stats()
        assert st["push_handoffs"] == 12 * rounds
        assert sum(l["push_handoffs"] for l in st["lanes"]) == 12 * rounds
        assert all(l["sends"] > 0 and l["push_handoffs"] > 0
                   for l in st["lanes"]), st["lanes"]
        assert st["lane_outstanding_bytes"] == 0, st
        assert all(c.quiet() for c in s._data_conns[0])
    finally:
        s.close()


def test_close_and_a_lane_shrink_leave_no_sender_behind(ps_server):
    """`close()` and a `wire_conns` shrink see the lanes' senders out:
    none of them is left among the live threads."""
    s = _session(ps_server(), partition_bytes=65536, wire_conns=4)
    try:
        x = np.arange(8 * 65536 // 4, dtype=np.float32)
        np.testing.assert_array_equal(s.push_pull(9, x), x)
        pool = s._data_conns[0]
        before = {c: c._send_thread for c in pool}
        s._resize_lanes(1)
        _until(lambda: len(pool) == 1, "the retired lanes never drained")
        kept = pool[0]
        retired = [t for c, t in before.items() if c is not kept]
        _until(lambda: not any(t.is_alive() for t in retired),
               "a retired lane's sender lives on")
        assert before[kept].is_alive()
        np.testing.assert_array_equal(s.push_pull(9, x * 2), x * 2)
    finally:
        s.close()
    assert not set(before.values()) & set(threading.enumerate())


# ---------------------------------------------------------------------------
# UDS fast path: bit-identical to TCP
# ---------------------------------------------------------------------------
def _run_trajectory(port, uds_path=""):
    """A deterministic multi-round mixed workload (raw rounds, onebit
    rounds with worker-side EF state, and a fusion-group push); returns
    every pulled array for bitwise comparison across transports."""
    s = _session(port, partition_bytes=65536, min_compress_bytes=0,
                 uds_path=uds_path)
    if uds_path:
        assert _transports(s) == {"uds"}
    else:
        assert _transports(s) == {"tcp"}
    rng = np.random.RandomState(3)
    outs = []
    try:
        raw = rng.randn(50000).astype(np.float32)     # 4 partitions
        for _ in range(3):
            outs.append(s.push_pull(40, raw).copy())
        s.register_compressor(41, {"compressor": "onebit",
                                   "ef": "vanilla"})
        comp = rng.randn(30000).astype(np.float32)
        for _ in range(3):
            outs.append(s.push_pull(41, comp).copy())
        items = [(50 + i, (rng.randn(2000) * (i + 1)).astype(np.float32), i)
                 for i in range(6)]
        for h in s.push_pull_group(items):
            outs.append(h.wait(30.0).copy())
    finally:
        s.close()
    return outs


def test_uds_tcp_bit_identical_raw_onebit_fusion_group(ps_server):
    """The acceptance contract for the AF_UNIX fast path: same framing,
    same bytes, bit-identical weight trajectories — raw f32, onebit (EF
    state exercised across rounds), and grouped fusion-style pushes all
    compared element-exact between a TCP run and a UDS run."""
    uds = f"/tmp/bps_uds_parity_{os.getpid()}"
    tcp_port = ps_server()
    uds_port = ps_server(extra_env={"BYTEPS_TPU_SERVER_UDS": uds})
    via_tcp = _run_trajectory(tcp_port)
    via_uds = _run_trajectory(uds_port, uds_path=uds)
    assert len(via_tcp) == len(via_uds)
    for i, (a, b) in enumerate(zip(via_tcp, via_uds)):
        np.testing.assert_array_equal(a, b, err_msg=f"round {i}")


def test_uds_falls_back_to_tcp_when_socket_missing(ps_server):
    port = ps_server()     # no UDS listener on this server
    s = _session(port, uds_path="/tmp/bps_uds_nonexistent")
    try:
        assert _transports(s) == {"tcp"}
        x = np.arange(1024, dtype=np.float32)
        np.testing.assert_array_equal(s.push_pull(2, x), x)
    finally:
        s.close()


# ---------------------------------------------------------------------------
# server scatter-receive path
# ---------------------------------------------------------------------------
def _raw_request(sock, cmd, key, payload=b"", dtype=0, flags=0, req_id=1,
                 worker_id=0):
    sock.sendall(_REQ.pack(cmd, dtype, flags, req_id, worker_id, key,
                           len(payload)) + payload)
    hdr = b""
    while len(hdr) < _RESP.size:
        got = sock.recv(_RESP.size - len(hdr))
        assert got, "server closed"
        hdr += got
    status, rid, rkey, ln = _RESP.unpack(hdr)
    body = b""
    while len(body) < ln:
        body += sock.recv(ln - len(body))
    assert status == 0, f"cmd {cmd} failed"
    return body


def test_scatter_and_buffered_merges_identical(ps_server):
    """Declared ordering (INIT before PUSH -> reader scatter-receives into
    the key's buffer, engine adopts by swap) and undeclared ordering
    (PUSH before any INIT -> classic buffered path) must produce
    identical merge results; server stats prove which path ran."""
    port = ps_server()
    x = np.arange(30000, dtype=np.float32) * 0.5

    # Declared: the normal session flow, several rounds so the adopted
    # store / scatter buffer recycle across publishes.
    s = _session(port)
    try:
        declared = [s.push_pull(3, x).copy() for _ in range(3)]
        stats = s.server_stats()
        assert stats["scatter_frames"] >= 3, stats
    finally:
        s.close()

    # Undeclared: hand-rolled frames, PUSH first.  The reader sees no
    # declared_len for the key and must take the buffered path — same
    # merge, same pull bytes.
    sock = socket.create_connection(("127.0.0.1", port))
    try:
        _raw_request(sock, CMD_PUSH, 4 << 16, x.tobytes())
        resp = _raw_request(sock, CMD_INIT, 4 << 16,
                            struct.pack("<QI", x.nbytes, 0))
        (completed,) = struct.unpack("<Q", resp)
        assert completed == 1    # the push-before-init round published
        pulled = np.frombuffer(
            _raw_request(sock, CMD_PULL, 4 << 16, flags=0), np.float32)
    finally:
        sock.close()

    for d in declared:
        np.testing.assert_array_equal(d, x)
    np.testing.assert_array_equal(pulled, x)


def test_scatter_two_worker_sum_exact(ps_server):
    """Scatter must stay a pure transport optimization under multi-worker
    merges: one worker's push rides the scatter lease, the other sums
    through a buffered frame, and the published round is bit-exact."""
    import threading

    port = ps_server(num_workers=2)
    rng = np.random.RandomState(5)
    a = rng.randn(40000).astype(np.float32)
    b = rng.randn(40000).astype(np.float32)
    out = {}

    def worker(wid, data):
        s = PSSession(["127.0.0.1"], [port], worker_id=wid, num_servers=1)
        try:
            for _ in range(3):
                out[wid] = s.push_pull(9, data).copy()
            if wid == 0:
                out["stats"] = s.server_stats()
        finally:
            s.close()

    ts = [threading.Thread(target=worker, args=(0, a)),
          threading.Thread(target=worker, args=(1, b))]
    [t.start() for t in ts]
    [t.join(timeout=60) for t in ts]
    expect = a + b
    np.testing.assert_array_equal(out[0], expect)
    np.testing.assert_array_equal(out[1], expect)
    assert out["stats"]["scatter_frames"] >= 1, out["stats"]


# ---------------------------------------------------------------------------
# socket tuning knob
# ---------------------------------------------------------------------------
def test_sock_buf_knob_applies_and_traffic_flows(ps_server):
    port = ps_server(extra_env={"BYTEPS_TPU_SOCK_BUF_KB": "256"})
    s = _session(port, sock_buf_kb=256)
    try:
        for pool in s._data_conns:
            for c in pool:
                # Kernel reports the (possibly doubled) effective size;
                # it must be at least what we asked for.
                snd = c.sock.getsockopt(socket.SOL_SOCKET,
                                        socket.SO_SNDBUF)
                assert snd >= 256 * 1024, snd
        x = np.arange(200000, dtype=np.float32)
        np.testing.assert_array_equal(s.push_pull(7, x), x)
    finally:
        s.close()


def test_decode_accepts_views_and_out_sink():
    """wire.decode must handle buffer views (pooled receives) with no
    bytes() snapshot and land directly in a caller-provided f32 sink."""
    from byteps_tpu.server import wire

    x = np.random.RandomState(0).randn(4096).astype(np.float32)
    blob = wire.WireCompressor({"compressor": "onebit"}).encode(1, x)
    ref = wire.decode(blob, x.size)
    for view in (bytearray(blob), memoryview(bytearray(blob))):
        np.testing.assert_array_equal(wire.decode(view, x.size), ref)
    sink = np.empty(x.size, np.float32)
    got = wire.decode(memoryview(bytearray(blob)), x.size, out=sink)
    assert got is sink
    np.testing.assert_array_equal(sink, ref)
    with pytest.raises(ValueError):
        wire.decode(blob, x.size, out=np.empty(x.size + 1, np.float32))
