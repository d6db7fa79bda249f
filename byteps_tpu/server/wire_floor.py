"""The floor of the PS wire on this host: the most bytes a second it moves
between this process and ANOTHER process over what a session's data lanes
are (docs/performance.md, "The floor").

`probe` starts a peer (`python -m byteps_tpu.server.wire_floor --peer`:
plain sockets, no jax, no server core), dials it over the session's
family (loopback TCP, or AF_UNIX where the session dialled that) with the
session's socket options (`tune`, which `_ServerConn._dial` uses too) and
as many lanes as a server's pool holds, and streams frames of the
session's partition size: out alone, in alone, and both ways at once on
every lane (`duplex`), which is what a round does, each the fastest of
`PASSES` passes.  No protocol, no
store, no sum: a phase is announced once a lane (`_PHASE`) and answered
by one byte when the peer holds every byte of it.  Each lane walks a
buffer of `_BUF_FRAMES` frames in either direction, in both processes,
so a frame is not served from the cache it was left in: a round reads
and writes its bytes once.

A worker that wrote a `comm.json` probes once, in `bps.shutdown()`
(`probe_at_shutdown`): after the last `ROUND`, outside any timed window.
Whatever fails (no child, a peer that does not answer within
`START_TIMEOUT_S`, phases that outlast `timeout_s`) gives None, writes
nothing and raises nothing.  `tools/wire_bench.py --echo-floor` compares
a session's goodput with the same probe.
"""

from __future__ import annotations

import json
import os
import select
import socket
import struct
import subprocess
import sys
import threading
import time
from typing import List, Optional

from ..common.logging import get_logger

_PHASE = struct.Struct("<QQI")  # bytes the peer receives, sends; frame bytes
_ACK = b"\x01"
_BUF_FRAMES = 8
PASSES = 3                  # of each rate; the fastest is the floor
MIN_BYTES = 1 << 30         # a direction moves this much, or a round's bytes
START_TIMEOUT_S = 5.0       # for the peer to say where it listens
PEER_LIFE_S = 120.0         # a peer whose parent forgot it ends itself

_probed = False             # probe_at_shutdown ran in this process


def tune(sock: socket.socket, sock_buf_kb: int) -> None:
    """BYTEPS_TPU_SOCK_BUF_KB (0 = kernel default) on both directions of
    a data lane; best-effort: the kernel clamps or doubles as it sees
    fit, and an EPERM on an exotic transport must not kill a dial."""
    if sock_buf_kb <= 0:
        return
    for opt in (socket.SO_SNDBUF, socket.SO_RCVBUF):
        try:
            sock.setsockopt(socket.SOL_SOCKET, opt, sock_buf_kb * 1024)
        except OSError:
            pass


def _recv_all(sock: socket.socket, view: memoryview) -> None:
    got = 0
    while got < len(view):
        r = sock.recv_into(view[got:], len(view) - got)
        if r == 0:
            raise ConnectionError("wire floor: the other side closed")
        got += r


class _Lane:
    """One socket and the two buffers its frames are cut from."""

    def __init__(self, sock: socket.socket):
        self.sock = sock
        self._bufs = {"send": bytearray(), "recv": bytearray()}

    def _walk(self, which: str, n: int, frame: int):
        """Views of `n` bytes in all, a frame at most each, round a
        buffer of `_BUF_FRAMES` frames (or `n`, if that is less)."""
        size = min(n, _BUF_FRAMES * frame)
        if len(self._bufs[which]) < size:
            self._bufs[which] = bytearray(size)
        view, off = memoryview(self._bufs[which]), 0
        while n > 0:
            k = min(frame, n, size - off)
            yield view[off:off + k]
            n -= k
            off = (off + k) % size

    def _send(self, n: int, frame: int) -> None:
        for view in self._walk("send", n, frame):
            self.sock.sendall(view)

    def exchange(self, n_send: int, n_recv: int, frame: int) -> None:
        """Send `n_send` bytes and receive `n_recv`, at once."""
        sender = None
        if n_send:
            sender = _Raising(self._send, n_send, frame)
            sender.start()
        try:
            for view in self._walk("recv", n_recv, frame):
                _recv_all(self.sock, view)
        finally:
            if sender is not None:
                sender.join_or_raise()


class _Raising(threading.Thread):
    """A thread whose exception its joiner gets."""

    def __init__(self, fn, *args):
        super().__init__(daemon=True, name="bps-wire-floor")
        self._fn, self._args, self.error = fn, args, None

    def run(self) -> None:
        try:
            self._fn(*self._args)
        except BaseException as e:     # handed to the joiner
            self.error = e

    def join_or_raise(self) -> None:
        self.join()
        if self.error is not None:
            raise self.error


# -- the peer ---------------------------------------------------------------
def _serve_lane(conn: socket.socket, sock_buf_kb: int) -> None:
    tune(conn, sock_buf_kb)
    lane, head = _Lane(conn), bytearray(_PHASE.size)
    try:
        while True:
            _recv_all(conn, memoryview(head))
            n_recv, n_send, frame = _PHASE.unpack(head)
            lane.exchange(n_send, n_recv, frame)
            conn.sendall(_ACK)
    except OSError:
        conn.close()


def _accept_loop(srv: socket.socket, tcp: bool, sock_buf_kb: int) -> None:
    while True:
        conn, _ = srv.accept()
        if tcp:
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        threading.Thread(target=_serve_lane, args=(conn, sock_buf_kb),
                         daemon=True).start()


def _peer_main(transport: str, sock_buf_kb: int) -> int:
    """Listen, say where on stdout, serve lanes until stdin closes."""
    if transport == "uds":
        # The abstract namespace: the same AF_UNIX stream path, and no
        # file to leave behind.
        srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        address = f"\0bps_wire_floor.{os.getpid()}"
        srv.bind(address)
        said = address[1:]
    else:
        srv = socket.socket()
        srv.bind(("127.0.0.1", 0))
        said = str(srv.getsockname()[1])
    srv.listen(64)
    life = threading.Timer(PEER_LIFE_S, os._exit, (1,))
    life.daemon = True
    life.start()
    threading.Thread(target=_accept_loop,
                     args=(srv, transport != "uds", sock_buf_kb),
                     daemon=True).start()
    sys.stdout.write(said + "\n")
    sys.stdout.flush()
    sys.stdin.buffer.read()     # the parent closes it, or is gone
    return 0


# -- the probe --------------------------------------------------------------
def _start_peer(transport: str, sock_buf_kb: int) -> subprocess.Popen:
    """The peer, started as a job starts its server child: this
    interpreter, this environment, the package's own directory."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return subprocess.Popen(
        [sys.executable, "-m", "byteps_tpu.server.wire_floor", "--peer",
         transport, str(sock_buf_kb)],
        cwd=root, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL)


def _dial(transport: str, said: str, sock_buf_kb: int) -> socket.socket:
    if transport == "uds":
        sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        sock.connect("\0" + said)
    else:
        sock = socket.create_connection(("127.0.0.1", int(said)))
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    tune(sock, sock_buf_kb)
    return sock


def _phase(lanes: List[_Lane], n_out: int, n_in: int, frame: int) -> float:
    """Seconds for every lane to send `n_out` bytes, receive `n_in` and
    hear that the peer holds what was sent."""
    def one(lane: _Lane) -> None:
        lane.sock.sendall(_PHASE.pack(n_out, n_in, frame))
        lane.exchange(n_out, n_in, frame)
        _recv_all(lane.sock, memoryview(bytearray(1)))

    threads = [_Raising(one, lane) for lane in lanes]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join_or_raise()
    return time.perf_counter() - t0


def _per_lane(nbytes: int, lanes: int, frame: int) -> int:
    """A lane's share of `nbytes`, rounded up to whole frames."""
    frames = -(-nbytes // frame)
    return -(-frames // lanes) * frame


def _probe(transport, lanes, frame, sock_buf_kb, bytes_out, bytes_in,
           timeout_s) -> dict:
    peer = _start_peer(transport, sock_buf_kb)
    socks: List[socket.socket] = []
    # The lanes block as a session's do, with no timeout of their own
    # (one costs a poll a call); a deadline that passes shuts them down,
    # which fails whatever call they are in.
    late = threading.Event()
    deadline = threading.Timer(
        timeout_s, lambda: late.set() or [_shut(s) for s in list(socks)])
    deadline.daemon = True
    try:
        if not select.select([peer.stdout], [], [], START_TIMEOUT_S)[0]:
            raise TimeoutError("wire floor: the peer said nothing")
        said = peer.stdout.readline().decode().strip()
        if not said:
            raise ConnectionError("wire floor: the peer did not start")
        deadline.start()
        for _ in range(lanes):
            socks.append(_dial(transport, said, sock_buf_kb))
        pool = [_Lane(s) for s in socks]
        n_out = _per_lane(bytes_out, lanes, frame)
        n_in = _per_lane(bytes_in, lanes, frame)
        # One warm pass: every buffer of both processes written once.
        _phase(pool, min(n_out, _BUF_FRAMES * frame),
               min(n_in, _BUF_FRAMES * frame), frame)
        result = {"transport": transport, "lanes": lanes,
                  "frame_bytes": frame, "sock_buf_kb": sock_buf_kb}
        for name, out, inn in (("out", n_out, 0), ("in", 0, n_in),
                               ("duplex", n_out, n_in)):
            if late.is_set():
                raise TimeoutError("wire floor: the deadline passed")
            # A floor is the most the host moves: a pass that another
            # tenant of the host slowed is not it.
            seconds = min(_phase(pool, out, inn, frame)
                          for _ in range(PASSES))
            moved = (out + inn) * lanes
            result[name] = {"bytes": moved, "seconds": seconds,
                            "GB_per_s": moved / seconds / 1e9}
        return result
    finally:
        deadline.cancel()
        for s in socks:
            s.close()
        _reap(peer)


def _shut(sock: socket.socket) -> None:
    try:
        sock.shutdown(socket.SHUT_RDWR)
    except OSError:
        pass


def _reap(peer: subprocess.Popen) -> None:
    for pipe in (peer.stdin, peer.stdout):
        try:
            pipe.close()
        except OSError:
            pass
    try:
        peer.wait(timeout=2.0)
    except subprocess.TimeoutExpired:
        peer.kill()
        peer.wait()


def probe(transport: str = "tcp", lanes: int = 4, frame_bytes: int = 4 << 20,
          sock_buf_kb: int = 0, bytes_out: int = MIN_BYTES,
          bytes_in: int = MIN_BYTES, timeout_s: float = 15.0
          ) -> Optional[dict]:
    """`{"out", "in", "duplex"}`, each `{"bytes", "seconds", "GB_per_s"}`,
    beside `lanes`, `transport`, `frame_bytes` and `sock_buf_kb`; or
    None where the probe could not be made."""
    try:
        return _probe("uds" if transport == "uds" else "tcp",
                      max(1, int(lanes)), max(1, int(frame_bytes)),
                      max(0, int(sock_buf_kb)), max(1, int(bytes_out)),
                      max(1, int(bytes_in)), timeout_s)
    except Exception as e:
        get_logger().debug("wire floor probe failed: %s", e)
        return None


def probe_session(sess, bytes_out: Optional[int] = None,
                  bytes_in: Optional[int] = None) -> Optional[dict]:
    """`probe` over what `sess`'s data lanes to its first server are.  A
    direction moves what the caller says or, left to itself, the last
    traced round's bytes where that is less than `MIN_BYTES`."""
    pool = sess._data_conns[0]
    last = sess.spans.last or {}

    def moved(asked: Optional[int], key: str) -> int:
        return asked or min(MIN_BYTES, last.get(key) or MIN_BYTES)

    return probe(transport=pool[0].transport, lanes=len(pool),
                 frame_bytes=sess.partition_bytes,
                 sock_buf_kb=sess.sock_buf_kb,
                 bytes_out=moved(bytes_out, "bytes_out"),
                 bytes_in=moved(bytes_in, "bytes_in"))


def probe_at_shutdown(sess, trace_dir: str) -> Optional[dict]:
    """Once a process: the floor over `sess`'s lanes into
    `<trace_dir>/wire_floor.json`, beside the `comm.json` there, and
    into the `bps_wire_floor_gbps` gauges."""
    global _probed
    if _probed:
        return None
    _probed = True
    result = probe_session(sess)
    if result is None:
        return None
    from ..common import telemetry
    try:
        with open(os.path.join(trace_dir, "wire_floor.json"), "w") as f:
            json.dump(result, f, indent=1)
    except OSError as e:
        get_logger().debug("wire floor not written: %s", e)
        return None
    telemetry.record_wire_floor(result)
    return result


if __name__ == "__main__":
    if len(sys.argv) != 4 or sys.argv[1] != "--peer":
        sys.exit("usage: python -m byteps_tpu.server.wire_floor --peer "
                 "<tcp|uds> <sock_buf_kb>")
    sys.exit(_peer_main(sys.argv[2], int(sys.argv[3])))
