"""Fault-tolerant PS transport tests.

Drives the REAL client/server wire code through programmable faults via
tools/chaos_proxy.py (a TCP forwarder between the PSSession and the C++
server), instead of mocking sockets: connection resets mid-payload,
silent blackholes, server kill-and-restart.  Asserts the recovery
invariants the transport promises — no double-counted push, no
stale-round pull, bit-identical sums vs an uninterrupted run — plus the
fail-fast default (BYTEPS_TPU_RECONNECT_ATTEMPTS=0 behaves exactly like
the pre-reconnect transport).
"""

import logging
import os
import socket
import struct
import subprocess
import sys
import threading
import time
from contextlib import contextmanager

import numpy as np
import pytest

from byteps_tpu.server.client import (
    PSSession, PSHandle, _ServerConn, _REQ, _RESP,
    CMD_PING, CMD_PULL, CMD_PUSH,
)
from byteps_tpu.common.logging import get_logger

from testutil import cpu_env, free_port

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))
from chaos_proxy import ChaosProxy  # noqa: E402


# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------
@pytest.fixture
def ps_server():
    """Yields a `start(...) -> port` callable with a live C++ server;
    kills every started server afterwards.  Same bind-race retry as
    tests/test_ps_server.py."""
    made = []

    def start(num_workers=1, async_mode=False, extra_env=None, port=None):
        last = None
        for _ in range(3):
            try:
                return _start_once(num_workers, async_mode, extra_env, port)
            except RuntimeError as e:
                last = e
                if port is not None:
                    raise      # pinned port: a bind failure is the answer
        raise last

    def _start_once(num_workers, async_mode, extra_env, port):
        port = port or free_port()
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

    start.procs = made      # the chaos smoke kills servers explicitly
    yield start
    for p in made:
        p.kill()
        p.wait()


class _LogCapture(logging.Handler):
    def __init__(self):
        super().__init__(logging.DEBUG)
        self.records = []

    def emit(self, record):
        self.records.append(record)

    def text(self) -> str:
        return "\n".join(r.getMessage() for r in self.records)


@contextmanager
def capture_logs(level=logging.DEBUG):
    """The byteps_tpu logger has propagate=False, so caplog can't see it;
    attach a recording handler directly."""
    lg = get_logger()
    h = _LogCapture()
    old_level = lg.level
    lg.addHandler(h)
    lg.setLevel(level)
    try:
        yield h
    finally:
        lg.removeHandler(h)
        lg.setLevel(old_level)


def _session(port, attempts=0, backoff_ms=50.0, stall_s=0.0, barrier_s=0.0,
             **kw):
    return PSSession(["127.0.0.1"], [port], worker_id=0, num_servers=1,
                     reconnect_attempts=attempts,
                     reconnect_backoff_ms=backoff_ms,
                     stall_timeout_s=stall_s,
                     barrier_timeout_s=barrier_s, **kw)


# ---------------------------------------------------------------------------
# chaos proxy sanity
# ---------------------------------------------------------------------------
def test_proxy_passthrough_is_transparent(ps_server):
    port = ps_server()
    with ChaosProxy("127.0.0.1", port) as proxy:
        s = _session(proxy.port)
        x = np.arange(1024, dtype=np.float32)
        np.testing.assert_array_equal(s.push_pull(3, x), x)
        s.close()
        st = proxy.stats()
        assert st["connections"] >= 1
        assert st["bytes_up"] > 0 and st["bytes_down"] > 0
        assert st["faults_fired"] == 0


# ---------------------------------------------------------------------------
# fail-fast default (BYTEPS_TPU_RECONNECT_ATTEMPTS=0) is unchanged
# ---------------------------------------------------------------------------
def test_default_fail_fast_on_drop(ps_server):
    """With the default reconnect_attempts=0 a dropped connection must
    fail pending requests exactly as before — no parking, no re-dial."""
    port = ps_server()
    with ChaosProxy("127.0.0.1", port) as proxy:
        s = _session(proxy.port)     # attempts=0: today's behavior
        x = np.ones(256, np.float32)
        np.testing.assert_array_equal(s.push_pull(9, x), x)
        proxy.kill_connections()
        time.sleep(0.3)              # let the receiver observe the RST
        with pytest.raises((ConnectionError, RuntimeError, TimeoutError)):
            s.push_pull(9, x)
        st = s.transport_stats()
        assert st["reconnects"] == 0
        assert st["parked_total"] == 0
        s.close()


def test_send_after_close_fast_fails_without_pending_leak(ps_server):
    """send() on a closed conn must raise ConnectionError immediately and
    must not leave an orphaned entry in the pending map."""
    port = ps_server()
    conn = _ServerConn("127.0.0.1", port)
    conn.close()
    with pytest.raises(ConnectionError):
        conn.send(CMD_PING, worker_id=0)
    assert conn._pending == {}
    assert conn.state() == "closed"


def test_recv_mid_payload_death_resolves_owning_future():
    """A connection that dies mid-payload must resolve the owning future
    with a ConnectionError — never orphan it into a silent hang."""
    lsock = socket.socket()
    lsock.bind(("127.0.0.1", 0))
    lsock.listen(1)
    port = lsock.getsockname()[1]

    def fake_server():
        c, _ = lsock.accept()
        hdr = c.recv(_REQ.size)
        _, _, _, req_id, _, key, _ = _REQ.unpack(hdr)
        # Claim a 1000-byte payload, deliver 100, die (mid-payload).
        c.sendall(_RESP.pack(0, req_id, key, 1000) + b"x" * 100)
        c.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                     struct.pack("ii", 1, 0))
        c.close()

    th = threading.Thread(target=fake_server, daemon=True)
    th.start()
    conn = _ServerConn("127.0.0.1", port)
    fut = conn.send(CMD_PULL, key=5, worker_id=0)
    with pytest.raises(ConnectionError, match="mid-payload"):
        fut.wait(10.0)
    conn.close()
    lsock.close()


def test_request_timeout_carries_context():
    """_Future.wait's TimeoutError must name cmd, key, req_id, and the
    elapsed time, not just 'timed out'."""
    lsock = socket.socket()
    lsock.bind(("127.0.0.1", 0))
    lsock.listen(1)
    port = lsock.getsockname()[1]
    accepted = []
    threading.Thread(
        target=lambda: accepted.append(lsock.accept()),
        daemon=True).start()      # accept, never respond
    conn = _ServerConn("127.0.0.1", port)
    with pytest.raises(TimeoutError) as ei:
        conn.request(CMD_PING, key=7, worker_id=0, timeout=0.2)
    msg = str(ei.value)
    assert "PING" in msg and "key=7" in msg
    assert "req_id=" in msg and "elapsed=" in msg
    conn.close()
    lsock.close()


# ---------------------------------------------------------------------------
# reconnect + replay
# ---------------------------------------------------------------------------
def test_reconnect_recovers_midpayload_reset_raw(ps_server):
    """A mid-payload connection reset during a push must recover within
    the backoff budget and produce the exact uninterrupted sum (single
    worker: the data itself) — no double count, no stale round."""
    port = ps_server()
    with ChaosProxy("127.0.0.1", port) as proxy:
        s = _session(proxy.port, attempts=8, backoff_ms=20.0, wire_conns=1)
        n = 256 * 1024              # 1 MiB partition
        warm = np.ones(n, np.float32)
        np.testing.assert_array_equal(s.push_pull(4, warm), warm)
        # Arm: the NEXT push dies 100 KB into its 1 MiB frame, then the
        # link heals (one-shot) — the reconnect-and-replay scenario.
        proxy.reset_after(100 * 1024)
        rng = np.random.RandomState(7)
        x = rng.randn(n).astype(np.float32)
        got = s.push_pull(4, x)
        np.testing.assert_array_equal(got, x)
        st = s.transport_stats()
        assert st["reconnects"] >= 1, st
        assert st["parked_total"] >= 1, st
        assert st["replayed_pushes"] + st["replayed_pulls"] >= 1, st
        assert st["parked_parts"] == 0, st
        assert proxy.stats()["faults_fired"] == 1
        # The session keeps working for later rounds.
        np.testing.assert_array_equal(s.push_pull(4, warm), warm)
        s.close()


class _CreditBook:
    """The scheduler queue with its credit counted: the bytes of what
    the dispatcher popped, and the bytes given back."""

    def __init__(self, queue):
        self._queue, self.popped, self.returned = queue, 0, 0

    def get(self):
        task = self._queue.get()
        if task is not None:
            self.popped += task[2]
        return task

    def report_finish(self, nbytes):
        self.returned += nbytes
        self._queue.report_finish(nbytes)

    def __getattr__(self, name):
        return getattr(self._queue, name)


def _on_pushes(conn, hook):
    """Run `hook(nth, conn)` on the lane's sender before its nth push
    goes to the socket; the hook may raise what `send` would."""
    real, seen = conn.send, []

    def send(cmd, key=0, *args, **kw):
        if cmd == CMD_PUSH:
            seen.append(key)
            hook(len(seen), conn)
        return real(cmd, key, *args, **kw)

    conn.send = send
    return seen


@pytest.mark.parametrize("attempts", [5, 0], ids=["reconnect", "fail-fast"])
def test_a_send_that_fails_in_a_lanes_sender(ps_server, attempts):
    """A push whose `send` raises on its lane's sender gives the queue's
    credit back once, and is parked and replayed where reconnect is on,
    or fails its handle where it is off; the session goes on."""
    s = _session(ps_server(), attempts=attempts, backoff_ms=20.0,
                 wire_conns=1, partition_bytes=65536, scheduling_credit=1)
    try:
        x = np.arange(3 * 65536 // 4, dtype=np.float32)     # 3 partitions
        np.testing.assert_array_equal(s.push_pull(4, x), x)
        book = s._queue = _CreditBook(s._queue)
        lane = s._data_conns[0][0]
        assert threading.current_thread() is not lane._send_thread

        def fail_first(nth, conn):
            assert threading.current_thread() is conn._send_thread
            if nth == 1:
                raise conn._lost_exc("injected send failure")

        _on_pushes(lane, fail_first)
        h = s.push_pull_async(4, x * 2)
        if attempts:
            np.testing.assert_array_equal(h.wait(30.0), x * 2)
        else:
            with pytest.raises(ConnectionError, match="injected"):
                h.wait(30.0)
            # the other partitions' round trips end before the next round
            deadline = time.time() + 10
            while s._inflight and time.time() < deadline:
                time.sleep(0.01)
        st = s.transport_stats()
        assert st["parked_total"] == st["replayed_pushes"] == (
            1 if attempts else 0), st
        assert st["parked_parts"] == 0, st
        # one partition of credit in all: a credit not given back would
        # stop the dispatcher here, one given back twice shows in the book
        y = np.full(2 * 65536 // 4, 3.0, np.float32)
        np.testing.assert_array_equal(s.push_pull(6, y), y)
        assert book.popped == book.returned > 0
        assert s.transport_stats()["lane_outstanding_bytes"] == 0
    finally:
        s.close()


def test_a_frame_waiting_in_a_dropped_lanes_hand_over_is_replayed(ps_server):
    """The lane drops while one push is on the socket and another waits
    in its sender's hand-over: both are parked and replayed, the sum is
    exact and the credit balances."""
    port = ps_server()
    with ChaosProxy("127.0.0.1", port) as proxy:
        s = _session(proxy.port, attempts=8, backoff_ms=300.0, wire_conns=1,
                     partition_bytes=256 * 1024)
        try:
            n = 4 * 256 * 1024 // 4                      # 4 partitions
            warm = np.ones(n, np.float32)
            np.testing.assert_array_equal(s.push_pull(4, warm), warm)
            book = s._queue = _CreditBook(s._queue)
            waited = []

            def hook(nth, conn):
                deadline = time.time() + 10
                if nth == 1:        # on the socket once one waits behind
                    while conn.frames_held < 2 and time.time() < deadline:
                        time.sleep(0.002)
                    waited.append(conn.frames_held)
                elif nth == 2:      # taken up once the lane is down
                    while conn.state() == "up" and time.time() < deadline:
                        time.sleep(0.002)
                    waited.append(conn.state())

            _on_pushes(s._data_conns[0][0], hook)
            proxy.reset_after(100 * 1024)
            x = np.random.RandomState(11).randn(n).astype(np.float32)
            np.testing.assert_array_equal(s.push_pull(4, x), x)
            assert waited[:2] == [2, "reconnecting"]
            st = s.transport_stats()
            assert st["reconnects"] >= 1, st
            assert st["parked_total"] >= 2, st
            assert st["replayed_pushes"] >= 2, st
            assert st["parked_parts"] == 0, st
            assert book.popped == book.returned
            assert st["lane_outstanding_bytes"] == 0, st
            np.testing.assert_array_equal(s.push_pull(4, warm), warm)
        finally:
            s.close()


def test_reconnect_compressed_bit_identical_to_uninterrupted(ps_server):
    """Wire-codec (onebit, stateful EF) traffic through a mid-round reset
    must produce bit-identical pulls to an uninterrupted run: the replay
    re-sends the already-encoded blob (never re-encodes, so worker EF
    state is consumed exactly once) and the server's seen-dedup plus the
    stale-round push guard stop any double merge."""
    port_a = ps_server()
    port_b = ps_server()
    n = 16 * 1024
    rng = np.random.RandomState(3)
    rounds = [rng.randn(n).astype(np.float32) for _ in range(4)]

    def run(port, fault_proxy=None):
        s = _session(port, attempts=8, backoff_ms=20.0, wire_conns=1,
                     min_compress_bytes=0)
        s.register_compressor(5, {"compressor": "onebit"})
        outs = []
        for i, g in enumerate(rounds):
            if fault_proxy is not None and i == 2:
                fault_proxy.reset_after(1024)    # mid-blob, one-shot
            outs.append(np.asarray(s.push_pull(5, g)))
        st = s.transport_stats()
        s.close()
        return outs, st

    ref, _ = run(port_a)
    with ChaosProxy("127.0.0.1", port_b) as proxy:
        got, st = run(proxy.port, fault_proxy=proxy)
        assert st["reconnects"] >= 1, st
    for i, (r, g) in enumerate(zip(ref, got)):
        np.testing.assert_array_equal(r, g, err_msg=f"round {i}")


def test_reconnect_fusion_group_exact(ps_server):
    """A grouped (fusion-bucket style) dispatch hit by a one-shot reset
    must deliver every member exactly once — mixed parked/unparked keys
    replay without cross-talk."""
    port = ps_server()
    with ChaosProxy("127.0.0.1", port) as proxy:
        s = _session(proxy.port, attempts=8, backoff_ms=20.0, wire_conns=1,
                     partition_bytes=128 * 1024)
        items = [(k, np.full(48 * 1024, float(k + 1), np.float32), 10 - k)
                 for k in range(6)]
        # Warm round: INITs + a healthy pass.
        for h, (k, v, _) in zip(s.push_pull_group(items), items):
            np.testing.assert_array_equal(h.wait(), v)
        proxy.reset_after(64 * 1024)     # dies partway through the group
        handles = s.push_pull_group(
            [(k, 2.0 * v, p) for k, v, p in items])
        for h, (k, v, _) in zip(handles, items):
            np.testing.assert_array_equal(h.wait(timeout=120.0), 2.0 * v,
                                          err_msg=f"key {k}")
        assert s.transport_stats()["reconnects"] >= 1
        s.close()


def test_two_workers_midround_reset_no_double_count(ps_server):
    """Worker 0 loses its connection mid-round (after its push may or may
    not have been acked); worker 1 then completes the round.  Worker 0's
    replay must reconcile against server state — the pulled sum is exactly
    a+b for both workers, never a+a+b (double count) and never a stale
    round."""
    port = ps_server(num_workers=2)
    n = 64 * 1024
    a = np.full(n, 3.0, np.float32)
    b = np.full(n, 5.0, np.float32)
    with ChaosProxy("127.0.0.1", port) as proxy:
        s0 = PSSession(["127.0.0.1"], [proxy.port], worker_id=0,
                       num_servers=1, reconnect_attempts=8,
                       reconnect_backoff_ms=20.0, wire_conns=1)
        s1 = PSSession(["127.0.0.1"], [port], worker_id=1, num_servers=1,
                       wire_conns=1)
        h0 = s0.push_pull_async(7, a)
        time.sleep(0.5)          # worker 0's push reaches the server
        proxy.kill_connections()
        time.sleep(0.2)
        out1 = {}
        t1 = threading.Thread(
            target=lambda: out1.update(r=s1.push_pull(7, b)))
        t1.start()
        got0 = h0.wait(timeout=120.0)
        t1.join(timeout=120)
        np.testing.assert_array_equal(got0, a + b)
        np.testing.assert_array_equal(out1["r"], a + b)
        s0.close()
        s1.close()


def test_stale_round_push_is_acked_and_dropped(ps_server):
    """Server-side replay guard: a push whose round flag belongs to an
    already-published round must be acked (the replaying worker moves on)
    but NEVER merged into the current round's sum."""
    port = ps_server()
    s = _session(port)
    n = 64
    a = np.full(n, 2.0, np.float32)
    b = np.full(n, 10.0, np.float32)
    conn = s.conns[0]
    conn.request(1, 8 << 16, struct.pack("<QI", a.nbytes, 0), worker_id=0)
    conn.request(2, 8 << 16, a.tobytes(), worker_id=0, flags=0)
    got = np.frombuffer(conn.request(3, 8 << 16, worker_id=0, flags=0),
                        np.float32)
    np.testing.assert_array_equal(got, a)
    # Replay of the published round-0 push: acked, dropped.
    conn.request(2, 8 << 16, a.tobytes(), worker_id=0, flags=0)
    # Round 1 must contain ONLY b (a double-counted replay would show as
    # a+b after COPY_FIRST adopted the stale payload).
    conn.request(2, 8 << 16, b.tobytes(), worker_id=0, flags=1)
    got = np.frombuffer(conn.request(3, 8 << 16, worker_id=0, flags=1),
                        np.float32)
    np.testing.assert_array_equal(got, b)
    s.close()


# ---------------------------------------------------------------------------
# stall watchdog
# ---------------------------------------------------------------------------
def test_watchdog_dumps_and_fails_blackholed_partition(ps_server):
    """A blackholed partition (bytes vanish, no error ever surfaces) must
    trip the stall watchdog within BYTEPS_TPU_STALL_TIMEOUT_S: the dump
    names the stuck key and the stuck handle fails loudly."""
    port = ps_server()
    with ChaosProxy("127.0.0.1", port) as proxy:
        s = _session(proxy.port, stall_s=1.5, wire_conns=1)
        x = np.ones(1024, np.float32)
        np.testing.assert_array_equal(s.push_pull(6, x), x)  # key inited
        proxy.blackhole(True)
        with capture_logs() as logs:
            t0 = time.monotonic()
            h = s.push_pull_async(6, x)
            with pytest.raises(RuntimeError, match="stalled"):
                h.wait(timeout=30.0)
            elapsed = time.monotonic() - t0
        assert elapsed < 15.0, f"watchdog too slow: {elapsed:.1f}s"
        dump = logs.text()
        assert "PS STALL" in dump
        assert f"key={6 << 16}" in dump
        assert s.transport_stats()["watchdog_trips"] == 1
        proxy.pass_through()
        s.close()


# ---------------------------------------------------------------------------
# barrier timeout / warning
# ---------------------------------------------------------------------------
def test_barrier_timeout_and_progress_warning(ps_server, monkeypatch):
    """bps.barrier() with BYTEPS_TPU_BARRIER_TIMEOUT_S set must fail
    loudly when a peer never arrives, after logging periodic 'still
    waiting' warnings (the old behavior was a silent infinite hang)."""
    from byteps_tpu.server import client as client_mod
    monkeypatch.setattr(client_mod, "BARRIER_WARN_INTERVAL_S", 0.3)
    port = ps_server(num_workers=2)      # peer 1 never shows up
    s = _session(port, barrier_s=1.2)
    with capture_logs(logging.WARNING) as logs:
        with pytest.raises(TimeoutError, match="gen=0"):
            s.barrier()
    assert "still waiting on barrier" in logs.text()
    s.close()


# ---------------------------------------------------------------------------
# handle timeout context + late-resolution discard
# ---------------------------------------------------------------------------
def test_handle_timeout_names_keys_and_discards_late_write():
    h = PSHandle((4,), np.float32, 1, np.zeros(4, np.float32))
    h._register_part(77)
    with pytest.raises(TimeoutError) as ei:
        h.wait(timeout=0.05)
    assert "77" in str(ei.value)
    assert h.failed()
    # A late completion must NOT write into the caller's buffer.
    assert h._store_result(0, np.ones(4, np.float32)) is False
    np.testing.assert_array_equal(h.out, np.zeros(4, np.float32))


def test_late_pull_after_wait_timeout_leaves_buffer_untouched(ps_server):
    """End-to-end: a pull that resolves after PSHandle.wait timed out is
    discarded — the caller's out buffer stays untouched (late writes into
    a buffer the caller may be reusing were the bug)."""
    port = ps_server()
    with ChaosProxy("127.0.0.1", port) as proxy:
        s = _session(proxy.port, wire_conns=1)
        x = np.full(1024, 4.0, np.float32)
        np.testing.assert_array_equal(s.push_pull(2, x), x)
        proxy.delay(400)                 # slower than the wait deadline
        h = s.push_pull_async(2, x)
        with pytest.raises(TimeoutError, match="outstanding partition"):
            h.wait(timeout=0.05)
        before = h.out.copy()
        proxy.pass_through()
        # Let the delayed pull finally arrive; it must be discarded.
        deadline = time.time() + 20
        while not h.done() and time.time() < deadline:
            time.sleep(0.1)
        np.testing.assert_array_equal(h.out, before)
        s.close()


# ---------------------------------------------------------------------------
# shutdown diagnostics + stats surfaces
# ---------------------------------------------------------------------------
def test_close_warns_on_wedged_dispatcher(ps_server):
    port = ps_server()
    s = _session(port)
    wedged = threading.Thread(target=time.sleep, args=(30,), daemon=True,
                              name="bps-ps-dispatch")
    wedged.start()
    real = s._dispatcher
    s._dispatcher = wedged
    s._join_timeout_s = 0.2
    with capture_logs(logging.WARNING) as logs:
        s.close()
    assert "did not exit" in logs.text()
    real.join(timeout=10)    # the real dispatcher saw _closed and exited


def test_reconnect_and_replay_over_uds(ps_server):
    """Kill-and-restart recovery over the AF_UNIX fast path: a push
    staged while the server is down parks, the conn re-dials the NEW
    socket file (the restarted server re-binds the same path), the
    replay rebases onto the fresh server, and the session stays on UDS
    throughout — PR 3 reconnect/replay semantics, new transport."""
    uds = f"/tmp/bps_uds_fault_{os.getpid()}"
    port = ps_server(extra_env={"BYTEPS_TPU_SERVER_UDS": uds})
    s = _session(port, attempts=20, backoff_ms=60.0, uds_path=uds)
    try:
        assert {c.transport for pool in s._data_conns
                for c in pool} == {"uds"}
        x = np.arange(5000, dtype=np.float32)
        np.testing.assert_array_equal(s.push_pull(2, x), x)
        victim = ps_server.procs[-1]
        victim.kill()
        victim.wait()
        h = s.push_pull_async(2, x * 3)          # parks during the outage
        ps_server(port=port, extra_env={"BYTEPS_TPU_SERVER_UDS": uds})
        np.testing.assert_array_equal(h.wait(timeout=60), x * 3)
        st = s.transport_stats()
        assert st["reconnects"] >= 1, st
        assert {c.transport for pool in s._data_conns
                for c in pool} == {"uds"}
    finally:
        s.close()


def test_transport_stats_shapes():
    import byteps_tpu as bps
    zero = bps.get_transport_stats()     # outside PS mode: all-zero shape
    assert zero == PSSession.TRANSPORT_ZERO_STATS
    assert zero is not PSSession.TRANSPORT_ZERO_STATS   # caller-safe copy


# ---------------------------------------------------------------------------
# slow chaos smoke: server kill-and-restart mid-training, loss parity
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_chaos_smoke_server_restart_loss_parity(ps_server):
    """Kill-and-restart the real server mid-training-step via the chaos
    proxy.  The worker rides out the outage (reconnect polls until the
    replacement binds the same port), rebases its rounds onto the fresh
    server, and the full training trajectory (weights after every step)
    is bit-identical to an uninterrupted run."""
    key, n, steps, kill_at = 12, 4096, 8, 3

    def train(port, server_ctl=None):
        s = _session(port, attempts=60, backoff_ms=50.0, wire_conns=1)
        w = np.full(n, 1.0, np.float32)
        traj = []
        for step in range(steps):
            if server_ctl is not None and step == kill_at:
                server_ctl()         # kill + restart mid-run
            g = 0.1 * w + float(step)
            summed = s.push_pull(key, g)     # 1 worker: sum == g
            w = w - 0.01 * summed
            traj.append(w.copy())
        st = s.transport_stats()
        s.close()
        return traj, st

    ref_port = ps_server()
    ref_traj, _ = train(ref_port)

    port = free_port()
    ps_server(port=port)
    with ChaosProxy("127.0.0.1", port) as proxy:
        victim = ps_server.procs[-1]     # the server behind the proxy

        def kill_and_restart():
            # Hard-kill the upstream (conns die mid-step), then bring a
            # fresh server up on the SAME port — state lost, round
            # counters reset, the rebase path must absorb it.
            victim.kill()
            victim.wait()
            proxy.kill_connections()
            ps_server(port=port)

        chaos_traj, st = train(proxy.port, server_ctl=kill_and_restart)
    assert st["reconnects"] >= 1, st
    assert len(chaos_traj) == len(ref_traj)
    for i, (r, c) in enumerate(zip(ref_traj, chaos_traj)):
        np.testing.assert_array_equal(r, c, err_msg=f"step {i}")
