"""The wire's nine metrics on a recording made on the CPU: data/wire
(three traced rounds of a five-leaf, 3.5 MB tree through
`bps.push_pull_tree` against a server child over four loopback lanes and
256 KiB partitions, by a program whose `ROUND` carries what its lanes
counted, and the `wire_floor.json` its worker left at shutdown); and
nothing on data/comm, data/spans, data/faults and data/stream, whose
programs had neither."""

import json
import os

import pytest

from benchmark.harness import manifest
from benchmark.harness.readers import reader
from benchmark.reduce import wire_counts
from benchmark.tests.test_d2h_hidden import DATA, _ctx

WIRE = os.path.join(DATA, "wire")
NAMES = ("ps.wire_GBps", "ps.wire_floor_GBps", "ps.wire_floor_share",
         "ps.send_lock_wait_ms", "ps.wire_calls_per_MB",
         "ps.pull_first_byte_ms", "ps.lane_idle_share", "ps.send_ms",
         "ps.recv_ms")
# The recording, by hand.  Three ROUNDs of 3,496,428 bytes each way; a
# PUSH or a PULL was open for 22,300 us of them in all.
BYTES, BUSY_US = 3 * 2 * 3496428, 22300
FLOOR = 1.1309341597518212      # wire_floor.json, duplex
EXPECTED = {
    "ps.wire_GBps": BYTES / BUSY_US / 1e3,
    "ps.wire_floor_GBps": FLOOR,
    "ps.wire_floor_share": 100 * BYTES / BUSY_US / 1e3 / FLOOR,
    # send_lock_wait_us 6843 + 1017 + 2514
    "ps.send_lock_wait_ms": 10374 / 3 / 1e3,
    # 28 sends and 42 receives a round
    "ps.wire_calls_per_MB": 3 * (28 + 42) / (BYTES / 1e6),
    # recv_first_byte_us 23495 + 6794 + 20869 over 14 pulls a round
    "ps.pull_first_byte_ms": 51158 / 42 / 1e3,
    # send_us 3779 + 2333 + 1647, recv_us 1281 + 1433 + 416
    "ps.send_ms": 7759 / 3 / 1e3,
    "ps.recv_ms": 3130 / 3 / 1e3,
    # lane_busy_us by lane over the rounds: 16435, 12257, 12347, 14897
    "ps.lane_idle_share": 100 * (1 - (16435 + 12257 + 12347 + 14897)
                                 / 4 / BUSY_US),
}


@pytest.mark.parametrize("name", NAMES)
def test_reader_gives_the_hand_computed_value(name):
    assert reader(name)(_ctx(WIRE)) == pytest.approx(EXPECTED[name],
                                                     rel=1e-12)


def test_rate_times_busy_time_is_the_rounds_bytes():
    ctx = _ctx(WIRE)
    per_round = reader("ps.wire_GBps")(ctx) * 1e9 \
        * reader("ps.wire_busy_ms")(ctx) / 1e3
    assert per_round == pytest.approx(2 * 3496428, rel=1e-12)
    assert 0 < reader("ps.wire_floor_share")(ctx) <= 100


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("trace", ["comm", "spans", "faults", "stream",
                                   ""])
def test_reader_says_nothing_without_counts_or_floor(name, trace):
    """The parent's `ROUND` has `bytes_out` and `bytes_in` and a wire
    that was busy, and no floor to read them against: no metric."""
    assert reader(name)(_ctx(os.path.join(DATA, trace))) is None


def test_counts_without_a_floor_and_a_floor_without_counts(tmp_path):
    (tmp_path / "0").mkdir()
    with open(os.path.join(WIRE, "0", "comm.json")) as f:
        doc = json.load(f)
    with open(tmp_path / "0" / "comm.json", "w") as f:
        json.dump(doc, f)
    ctx = _ctx(str(tmp_path))
    assert reader("ps.wire_GBps")(ctx) == pytest.approx(
        EXPECTED["ps.wire_GBps"])
    assert reader("ps.wire_floor_GBps")(ctx) is None
    assert reader("ps.wire_floor_share")(ctx) is None
    # one ROUND of an older program among them: nothing to sum
    for e in doc["traceEvents"]:
        if e.get("tid") == "ROUND":
            for k in ("send_calls", "lanes", "lane_busy_us"):
                del e["args"][k]
            break
    assert wire_counts.reduce(doc["traceEvents"]) is None


def test_the_manifest_lists_them_for_the_ps_cell():
    cell = manifest.load_cell("gpt2-medium.ps-joint-1chip")
    listed = {m["name"]: m for m in cell.per_layer}
    for name in NAMES:
        assert listed[name]["moves"] == "ps_tokens_per_s"
        assert listed[name]["layer"] == "PS wire + server"
