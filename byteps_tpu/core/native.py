"""ctypes bindings for the native host core, with a pure-Python fallback.

The reference binds its C++ core to Python per-framework via pybind11/ctypes
(reference: byteps/common/__init__.py:52-77 dlopens c_lib).  pybind11 is not
available in this image, so we use a flat C ABI + ctypes.  On a host with no
toolchain we degrade to `_PyCore`, a behaviorally identical Python
implementation — everything stays usable, just without native speed.  A
compile error is not that case: it raises.
"""

from __future__ import annotations

import ctypes
import threading
import time
from typing import List, Optional, Tuple

from ..common.logging import get_logger


class _CCore:
    """ctypes facade over libbyteps_core.so."""

    def __init__(self, lib: ctypes.CDLL):
        self._lib = lib
        # Python-side mirror of the native tracer's on/off flag: hot paths
        # (the PS dispatcher) read this attribute instead of crossing the
        # ctypes boundary and taking the tracer mutex per partition.
        self.trace_on = False
        L = lib
        L.bps_declare_tensor.argtypes = [ctypes.c_char_p]
        L.bps_declare_tensor.restype = ctypes.c_int32
        L.bps_get_declared_key.argtypes = [ctypes.c_char_p]
        L.bps_get_declared_key.restype = ctypes.c_int32
        L.bps_num_declared.restype = ctypes.c_int32
        L.bps_declared_name.argtypes = [ctypes.c_int32, ctypes.c_char_p,
                                        ctypes.c_int32]
        L.bps_declared_name.restype = ctypes.c_int32
        L.bps_reset_registry.restype = None
        L.bps_encode_key.argtypes = [ctypes.c_int32, ctypes.c_int32]
        L.bps_encode_key.restype = ctypes.c_uint64
        L.bps_decode_declared_key.argtypes = [ctypes.c_uint64]
        L.bps_decode_declared_key.restype = ctypes.c_int32
        L.bps_decode_part_idx.argtypes = [ctypes.c_uint64]
        L.bps_decode_part_idx.restype = ctypes.c_int32
        L.bps_align.argtypes = [ctypes.c_int64, ctypes.c_int64]
        L.bps_align.restype = ctypes.c_int64
        L.bps_partition_count.argtypes = [ctypes.c_int64, ctypes.c_int64]
        L.bps_partition_count.restype = ctypes.c_int32
        L.bps_partition_bounds.argtypes = [
            ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64)]
        L.bps_partition_bounds.restype = ctypes.c_int32
        L.bps_key_to_server.argtypes = [ctypes.c_uint64, ctypes.c_int32,
                                        ctypes.c_char_p]
        L.bps_key_to_server.restype = ctypes.c_int32
        L.bps_queue_create.argtypes = [ctypes.c_int32, ctypes.c_int64]
        L.bps_queue_create.restype = ctypes.c_void_p
        L.bps_queue_destroy.argtypes = [ctypes.c_void_p]
        L.bps_queue_add.argtypes = [ctypes.c_void_p, ctypes.c_uint64,
                                    ctypes.c_int32, ctypes.c_int64]
        L.bps_queue_get.argtypes = [ctypes.c_void_p,
                                    ctypes.POINTER(ctypes.c_uint64),
                                    ctypes.POINTER(ctypes.c_int32)]
        L.bps_queue_get.restype = ctypes.c_int64
        L.bps_queue_get_key.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        L.bps_queue_get_key.restype = ctypes.c_int64
        L.bps_queue_report_finish.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        L.bps_queue_pending.argtypes = [ctypes.c_void_p]
        L.bps_queue_pending.restype = ctypes.c_int64
        L.bps_telemetry_set_window_us.argtypes = [ctypes.c_int64]
        L.bps_telemetry_record.argtypes = [ctypes.c_int64]
        L.bps_telemetry_speed_mbps.restype = ctypes.c_double
        L.bps_trace_enable.argtypes = [ctypes.c_int32]
        L.bps_trace_now_us.restype = ctypes.c_int64
        L.bps_trace_record.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                       ctypes.c_int64, ctypes.c_int64]
        L.bps_trace_record_part.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int32]
        L.bps_trace_record_args.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int32]
        L.bps_trace_count.restype = ctypes.c_int64
        L.bps_trace_dump.argtypes = [ctypes.c_char_p, ctypes.c_int32]
        L.bps_trace_dump.restype = ctypes.c_int32
        L.bps_handle_allocate.restype = ctypes.c_int32
        L.bps_handle_mark_done.argtypes = [ctypes.c_int32]
        L.bps_handle_poll.argtypes = [ctypes.c_int32]
        L.bps_handle_poll.restype = ctypes.c_int32
        L.bps_handle_release.argtypes = [ctypes.c_int32]

    # -- registry --
    def declare_tensor(self, name: str) -> int:
        return self._lib.bps_declare_tensor(name.encode())

    def get_declared_key(self, name: str) -> int:
        return self._lib.bps_get_declared_key(name.encode())

    def num_declared(self) -> int:
        return self._lib.bps_num_declared()

    def declared_name(self, idx: int) -> Optional[str]:
        buf = ctypes.create_string_buffer(1024)
        n = self._lib.bps_declared_name(idx, buf, 1024)
        return None if n < 0 else buf.value.decode()

    def reset_registry(self) -> None:
        self._lib.bps_reset_registry()

    # -- keys / partitioning --
    def encode_key(self, declared_key: int, part_idx: int) -> int:
        return self._lib.bps_encode_key(declared_key, part_idx)

    def decode_key(self, key: int) -> Tuple[int, int]:
        return (self._lib.bps_decode_declared_key(key),
                self._lib.bps_decode_part_idx(key))

    def partition_bounds(self, nbytes: int,
                         partition_bytes: int) -> List[Tuple[int, int]]:
        n = self._lib.bps_partition_count(nbytes, partition_bytes)
        offs = (ctypes.c_int64 * n)()
        lens = (ctypes.c_int64 * n)()
        self._lib.bps_partition_bounds(nbytes, partition_bytes, offs, lens)
        return [(offs[i], lens[i]) for i in range(n)]

    def key_to_server(self, key: int, num_servers: int,
                      hash_fn: str = "djb2") -> int:
        return self._lib.bps_key_to_server(key, num_servers, hash_fn.encode())

    # -- scheduled queue --
    def queue_create(self, credit_bytes: int = 0) -> "NativeQueue":
        return NativeQueue(self._lib, credit_bytes)

    # -- telemetry --
    def telemetry_record(self, nbytes: int) -> None:
        self._lib.bps_telemetry_record(nbytes)

    def telemetry_speed_mbps(self) -> float:
        return self._lib.bps_telemetry_speed_mbps()

    def telemetry_set_window_us(self, us: int) -> None:
        self._lib.bps_telemetry_set_window_us(us)

    def telemetry_reset(self) -> None:
        self._lib.bps_telemetry_reset()

    # -- tracing --
    def trace_enable(self, on: bool) -> None:
        self.trace_on = bool(on)
        self._lib.bps_trace_enable(1 if on else 0)

    def trace_now_us(self) -> int:
        return self._lib.bps_trace_now_us()

    def trace_record(self, name: str, stage: str, ts_us: int,
                     dur_us: int) -> None:
        self._lib.bps_trace_record(name.encode(), stage.encode(), ts_us, dur_us)

    def trace_record_part(self, name: str, stage: str, ts_us: int,
                          dur_us: int, key: int, nbytes: int,
                          priority: int) -> None:
        """Per-partition span (QUEUE/PUSH/PULL) with key/bytes/priority args
        (reference: per-partition spans in global.cc:463-579)."""
        self._lib.bps_trace_record_part(name.encode(), stage.encode(), ts_us,
                                        dur_us, key, nbytes, priority)

    def trace_record_args(self, name: str, stage: str, ts_us: int,
                          dur_us: int, args: dict) -> None:
        """Span whose Chrome-trace args are the named integers of `args`
        (the main-thread stage spans, common/stage_spans.py).  A value
        that is a list of integers goes as its name with `[]` once an
        element, which the dump writes back as one list."""
        flat = [(k + "[]", x) if isinstance(v, list) else (k, v)
                for k, v in args.items()
                for x in (v if isinstance(v, list) else (v,))]
        vals = (ctypes.c_int64 * len(flat))(*(v for _, v in flat))
        self._lib.bps_trace_record_args(
            name.encode(), stage.encode(), ts_us, dur_us,
            ",".join(k for k, _ in flat).encode(), vals, len(flat))

    def trace_count(self) -> int:
        return self._lib.bps_trace_count()

    def trace_dump(self, path: str, rank: int) -> int:
        return self._lib.bps_trace_dump(path.encode(), rank)

    # -- handles --
    def handle_allocate(self) -> int:
        return self._lib.bps_handle_allocate()

    def handle_mark_done(self, h: int) -> None:
        self._lib.bps_handle_mark_done(h)

    def handle_poll(self, h: int) -> int:
        return self._lib.bps_handle_poll(h)

    def handle_release(self, h: int) -> None:
        self._lib.bps_handle_release(h)


class NativeQueue:
    """Priority ScheduledQueue handle (native)."""

    def __init__(self, lib: ctypes.CDLL, credit_bytes: int):
        self._lib = lib
        self._q = lib.bps_queue_create(1 if credit_bytes > 0 else 0,
                                       credit_bytes)

    def add(self, key: int, priority: int, nbytes: int) -> None:
        self._lib.bps_queue_add(self._q, key, priority, nbytes)

    def get(self) -> Optional[Tuple[int, int, int]]:
        """Returns (key, priority, nbytes) or None."""
        k = ctypes.c_uint64()
        p = ctypes.c_int32()
        n = self._lib.bps_queue_get(self._q, ctypes.byref(k), ctypes.byref(p))
        return None if n < 0 else (k.value, p.value, n)

    def get_key(self, key: int) -> Optional[int]:
        n = self._lib.bps_queue_get_key(self._q, key)
        return None if n < 0 else n

    def report_finish(self, nbytes: int) -> None:
        self._lib.bps_queue_report_finish(self._q, nbytes)

    def pending(self) -> int:
        return self._lib.bps_queue_pending(self._q)

    def __del__(self):
        try:
            self._lib.bps_queue_destroy(self._q)
        except Exception:
            pass


# ---------------------------------------------------------------------------
# Pure-Python fallback with identical semantics (used when g++ is unavailable).
# ---------------------------------------------------------------------------
class _PyQueue:
    def __init__(self, credit_bytes: int = 0):
        self._tasks: list = []
        self._credit_enabled = credit_bytes > 0
        self._credit = credit_bytes
        self._lock = threading.Lock()

    def add(self, key, priority, nbytes):
        with self._lock:
            self._tasks.append((key, priority, nbytes))
            self._tasks.sort(key=lambda t: (-t[1], t[0]))

    def get(self):
        with self._lock:
            for i, (k, p, n) in enumerate(self._tasks):
                if self._credit_enabled and n > self._credit:
                    continue
                self._tasks.pop(i)
                if self._credit_enabled:
                    self._credit -= n
                return (k, p, n)
            return None

    def get_key(self, key):
        with self._lock:
            for i, (k, p, n) in enumerate(self._tasks):
                if k == key:
                    # Same eligibility check as get(): an oversized task
                    # stays queued instead of driving the credit negative.
                    if self._credit_enabled and n > self._credit:
                        return None
                    self._tasks.pop(i)
                    if self._credit_enabled:
                        self._credit -= n
                    return n
            return None

    def report_finish(self, nbytes):
        with self._lock:
            if self._credit_enabled:
                self._credit += nbytes

    def pending(self):
        with self._lock:
            return len(self._tasks)


class _PyCore:
    def __init__(self):
        self.trace_on = False  # same hot-path gate as _CCore
        self._name2key: dict = {}
        self._names: list = []
        self._lock = threading.Lock()
        self._tel_events: list = []
        self._tel_window_us = 10_000_000
        self._trace_on = False
        self._trace_events: list = []
        self._next_handle = 0
        self._handles: dict = {}

    def declare_tensor(self, name):
        with self._lock:
            if name in self._name2key:
                return self._name2key[name]
            key = len(self._names)
            self._name2key[name] = key
            self._names.append(name)
            return key

    def get_declared_key(self, name):
        with self._lock:
            return self._name2key.get(name, -1)

    def num_declared(self):
        with self._lock:
            return len(self._names)

    def declared_name(self, idx):
        with self._lock:
            return self._names[idx] if 0 <= idx < len(self._names) else None

    def reset_registry(self):
        with self._lock:
            self._name2key.clear()
            self._names.clear()

    def encode_key(self, declared_key, part_idx):
        return (declared_key << 16) | (part_idx & 0xFFFF)

    def decode_key(self, key):
        return key >> 16, key & 0xFFFF

    def partition_bounds(self, nbytes, partition_bytes):
        if nbytes <= 0:
            return [(0, max(nbytes, 0))]
        out, off = [], 0
        while off < nbytes:
            ln = min(partition_bytes, nbytes - off)
            out.append((off, ln))
            off += ln
        return out

    def key_to_server(self, key, num_servers, hash_fn="djb2"):
        if num_servers <= 0:
            return 0
        s = str(key)

        def djb2():
            h = 5381
            for c in s:
                h = (((h << 5) + h) + ord(c)) & 0xFFFFFFFFFFFFFFFF
            return h

        def sdbm():
            h = 0
            for c in s:
                h = (ord(c) + (h << 6) + (h << 16) - h) & 0xFFFFFFFFFFFFFFFF
            return h

        if hash_fn == "naive":
            h = key
        elif hash_fn == "sdbm":
            h = sdbm()
        elif hash_fn == "mixed":
            h = djb2() ^ sdbm()  # full 64-bit XOR, matching core.cc
        else:
            h = djb2()
        return h % num_servers

    def queue_create(self, credit_bytes=0):
        return _PyQueue(credit_bytes)

    def telemetry_set_window_us(self, us):
        self._tel_window_us = us

    def telemetry_record(self, nbytes):
        t = time.monotonic_ns() // 1000
        self._tel_events.append((t, nbytes))
        cutoff = t - self._tel_window_us
        self._tel_events = [e for e in self._tel_events if e[0] >= cutoff]

    def telemetry_speed_mbps(self):
        t = time.monotonic_ns() // 1000
        cutoff = t - self._tel_window_us
        total = sum(b for ts, b in self._tel_events if ts >= cutoff)
        return (total / 1e6) / (self._tel_window_us / 1e6)

    def telemetry_reset(self):
        self._tel_events.clear()

    def trace_enable(self, on):
        self.trace_on = self._trace_on = bool(on)

    def trace_now_us(self):
        return time.monotonic_ns() // 1000

    def trace_record(self, name, stage, ts_us, dur_us):
        if self._trace_on:
            self._trace_events.append((name, stage, ts_us, dur_us, None))

    def trace_record_part(self, name, stage, ts_us, dur_us, key, nbytes,
                          priority):
        if self._trace_on:
            self._trace_events.append(
                (name, stage, ts_us, dur_us,
                 {"key": key, "bytes": nbytes, "priority": priority}))

    def trace_record_args(self, name, stage, ts_us, dur_us, args):
        if self._trace_on:
            self._trace_events.append(
                (name, stage, ts_us, dur_us,
                 {k: [int(x) for x in v] if isinstance(v, list) else int(v)
                  for k, v in args.items()}))

    def trace_count(self):
        return len(self._trace_events)

    def trace_dump(self, path, rank):
        import json
        events = [{"name": n, "cat": "comm", "ph": "X", "ts": ts, "dur": d,
                   "pid": rank, "tid": stage,
                   **({"args": args} if args else {})}
                  for (n, stage, ts, d, args) in self._trace_events]
        with open(path, "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
        self._trace_events.clear()
        return 0

    def handle_allocate(self):
        with self._lock:
            h = self._next_handle
            self._next_handle += 1
            self._handles[h] = 0
            return h

    def handle_mark_done(self, h):
        with self._lock:
            self._handles[h] = 1

    def handle_poll(self, h):
        with self._lock:
            return self._handles.get(h, -1)

    def handle_release(self, h):
        with self._lock:
            self._handles.pop(h, None)


_core = None
_core_lock = threading.Lock()


def get_core():
    """Returns the process-wide core: native, or the Python mirror on a
    host with no toolchain.  A compile *error* is not that case and
    propagates (build.BuildError carries g++'s message)."""
    global _core
    with _core_lock:
        if _core is None:
            from . import build
            try:
                path = build.build()
            except FileNotFoundError as e:  # no g++ on this host
                get_logger().warning(
                    "native core unavailable (%s); using Python fallback", e)
                _core = _PyCore()
            else:
                _core = _CCore(ctypes.CDLL(path))
                get_logger().debug("loaded native core from %s", path)
        return _core


def is_native() -> bool:
    return isinstance(get_core(), _CCore)
