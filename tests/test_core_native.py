"""Unit tests for the native host core (registry, keys, partitioning,
scheduled queue, ready table, telemetry, tracing, handles).

The reference has no isolated C++ unit tests (SURVEY §4); we add them.
"""

import os

import pytest

from byteps_tpu.core.native import get_core, is_native, _PyCore


@pytest.fixture(params=["native", "python"])
def core(request):
    if request.param == "native":
        c = get_core()
        if not is_native():
            pytest.skip("native core unavailable")
        c.reset_registry()
        return c
    return _PyCore()


def test_declare_is_deterministic_and_idempotent(core):
    k0 = core.declare_tensor("grad.layer0")
    k1 = core.declare_tensor("grad.layer1")
    assert (k0, k1) == (0, 1)
    # Re-declaring returns the original key (elastic-resume invariant,
    # reference: operations.cc:107-119).
    assert core.declare_tensor("grad.layer0") == 0
    assert core.get_declared_key("grad.layer1") == 1
    assert core.get_declared_key("missing") == -1
    assert core.num_declared() == 2
    assert core.declared_name(0) == "grad.layer0"
    assert core.declared_name(5) is None


def test_key_encoding_roundtrip(core):
    # declared_key << 16 | part (reference: operations.cc:301-311).
    key = core.encode_key(7, 3)
    assert key == (7 << 16) | 3
    assert core.decode_key(key) == (7, 3)


def test_partition_bounds(core):
    # 10 MB tensor at 4 MB partitions -> 4+4+2.
    mb = 1024 * 1024
    bounds = core.partition_bounds(10 * mb, 4 * mb)
    assert bounds == [(0, 4 * mb), (4 * mb, 4 * mb), (8 * mb, 2 * mb)]
    # Small tensor: single partition.
    assert core.partition_bounds(100, 4 * mb) == [(0, 100)]


def test_key_to_server_deterministic_and_spread(core):
    placements = [core.key_to_server(core.encode_key(i, 0), 4)
                  for i in range(64)]
    assert all(0 <= p < 4 for p in placements)
    assert len(set(placements)) > 1  # not all on one server
    # Deterministic across calls.
    assert placements == [core.key_to_server(core.encode_key(i, 0), 4)
                          for i in range(64)]
    for fn in ("naive", "djb2", "sdbm", "mixed"):
        assert 0 <= core.key_to_server(12345, 7, fn) < 7


def test_scheduled_queue_priority_order(core):
    q = core.queue_create()
    q.add(key=10, priority=-10, nbytes=100)
    q.add(key=1, priority=-1, nbytes=100)
    q.add(key=5, priority=-5, nbytes=100)
    # Higher priority first (reference: scheduled_queue.cc:82-102).
    assert q.get()[0] == 1
    assert q.get()[0] == 5
    assert q.get()[0] == 10
    assert q.get() is None


def test_scheduled_queue_tie_break_by_key(core):
    q = core.queue_create()
    q.add(key=9, priority=0, nbytes=1)
    q.add(key=2, priority=0, nbytes=1)
    assert q.get()[0] == 2
    assert q.get()[0] == 9


def test_scheduled_queue_credit_flow_control(core):
    # Credit budget caps bytes in flight (reference:
    # scheduled_queue.cc:26-46,136-139,197-203).
    q = core.queue_create(credit_bytes=150)
    q.add(key=1, priority=0, nbytes=100)
    q.add(key=2, priority=0, nbytes=100)
    assert q.get()[0] == 1          # 100 in flight, 50 credit left
    assert q.get() is None          # second task (100b) exceeds credit
    q.report_finish(100)            # credit returned
    assert q.get()[0] == 2


def test_scheduled_queue_get_key(core):
    q = core.queue_create()
    q.add(key=1, priority=0, nbytes=10)
    q.add(key=2, priority=0, nbytes=20)
    assert q.get_key(2) == 20
    assert q.get_key(2) is None
    assert q.pending() == 1


def test_scheduled_queue_get_key_respects_credit(core):
    # get_key must apply the same credit-eligibility check as get():
    # popping an oversized task would drive the credit negative and stall
    # every later get() until enough finishes were reported.
    q = core.queue_create(credit_bytes=150)
    q.add(key=1, priority=0, nbytes=100)
    q.add(key=2, priority=0, nbytes=100)
    assert q.get_key(1) == 100      # 100 in flight, 50 credit left
    assert q.get_key(2) is None     # 100b exceeds remaining credit
    assert q.pending() == 1         # ...and the task stays queued
    q.report_finish(100)
    assert q.get_key(2) == 100
    q.report_finish(100)
    # A small eligible task still pops while a big one is queued.
    q.add(key=3, priority=0, nbytes=1000)
    q.add(key=4, priority=0, nbytes=10)
    assert q.get_key(3) is None
    assert q.get_key(4) == 10


def test_telemetry_speed(core):
    core.telemetry_reset()
    core.telemetry_set_window_us(1_000_000)
    for _ in range(10):
        core.telemetry_record(1_000_000)  # 10 MB within the window
    assert core.telemetry_speed_mbps() == pytest.approx(10.0, rel=0.2)
    core.telemetry_reset()
    assert core.telemetry_speed_mbps() == 0.0
    core.telemetry_set_window_us(10_000_000)


def test_trace_record_and_dump(core, tmp_path):
    core.trace_enable(True)
    t0 = core.trace_now_us()
    core.trace_record("Gradient.layer0", "PUSH_PULL", t0, 123)
    core.trace_record("Gradient.layer1", "REDUCE", t0 + 10, 45)
    assert core.trace_count() == 2
    path = str(tmp_path / "comm.json")
    assert core.trace_dump(path, rank=0) == 0
    import json
    with open(path) as f:
        data = json.load(f)
    events = data["traceEvents"]
    assert len(events) == 2
    assert events[0]["name"] == "Gradient.layer0"
    assert events[0]["ph"] == "X"
    assert events[0]["dur"] == 123
    assert core.trace_count() == 0  # dump clears
    core.trace_enable(False)


def test_trace_record_args_dumps_named_integers(core, tmp_path):
    """A stage span's args object is its named integers, in both cores
    alike (beyond 32 bits and negative included), and off is off."""
    import json
    args = {"round": 3, "key": -1, "bytes": 5 * 2**32, "units": 0}
    core.trace_record_args("dropped", "D2H", 1, 2, args)
    assert core.trace_count() == 0
    core.trace_enable(True)
    core.trace_record_args('t["a"]', "D2H", 10, 20, args)
    core.trace_record_part("t.part0", "PUSH", 30, 5, 7 << 16, 64, 2)
    core.trace_record_args("t", "PACK", 40, 1, {"round": 3})
    core.trace_enable(False)
    path = str(tmp_path / "comm.json")
    assert core.trace_dump(path, rank=1) == 0
    with open(path) as f:
        d2h, push, pack = json.load(f)["traceEvents"]
    assert (d2h["name"], d2h["tid"], d2h["ts"], d2h["dur"], d2h["pid"]) == (
        't["a"]', "D2H", 10, 20, 1)
    assert d2h["args"] == args
    assert push["args"] == {"key": 7 << 16, "bytes": 64, "priority": 2}
    assert pack["args"] == {"round": 3}


def test_handle_manager(core):
    h = core.handle_allocate()
    assert core.handle_poll(h) == 0
    core.handle_mark_done(h)
    assert core.handle_poll(h) == 1
    core.handle_release(h)
    assert core.handle_poll(h) == -1
