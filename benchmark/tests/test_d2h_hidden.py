"""`ps.d2h_hidden_ms` on recordings made on the CPU: data/stream (two
traced rounds of a five-leaf, 3.6 MB tree through `bps.push_pull_tree`
against a server child, by a program that queues each unit as soon as
it is staged; the leaves were still being computed when the round
began, so a unit's `D2H` waits while the one before it is on the wire),
data/spans (a program that queued the round after its last copy) and
data/comm (a program without stage spans)."""

import json
import os

import pytest

from benchmark.harness import tracecap
from benchmark.harness.readers import reader
from benchmark.reduce import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NAME = "ps.d2h_hidden_ms"


def _ctx(trace_dir):
    return tracecap.Context(
        trace=xplane.Trace(ops=[], async_ops=[], host=[]), n_steps=2,
        first_step=0, n_chips=1, samples_per_step=1, family=None, peaks={},
        extras={}, dir=trace_dir)


def _events(name):
    with open(os.path.join(DATA, name, "0", "comm.json")) as f:
        return [e for e in json.load(f)["traceEvents"] if e.get("ph") == "X"
                and e.get("pid") == 0]


def test_streamed_rounds_give_the_hand_computed_value():
    """Microseconds less 13,600,000,000.  Round 1: the last `D2H` ends
    at 686,670 and the first `PUSH` starts at 686,787: nothing shared.
    Round 2: the wire is busy without a hole from 815,700 (`PUSH` of c)
    to 823,680 (`PULL` of a); `D2H` of b 815,559-817,701 shares 2,001
    with it, of a 818,053-820,386 all 2,333, of the bucket
    820,555-820,571 all 16, and of c (ends 815,008) nothing."""
    assert reader(NAME)(_ctx(os.path.join(DATA, "stream"))) == pytest.approx(
        (0 + 2001 + 2333 + 16) / 2 / 1e3, rel=1e-12)


def test_value_is_a_count_of_the_shared_microseconds():
    events = _events("stream")
    rounds = [(e["ts"], e["ts"] + e["dur"], e["args"]["round"])
              for e in events if e["tid"] == "ROUND"]
    assert [(e["args"]["units"], e["args"]["units_early"])
            for e in events if e["tid"] == "ROUND"] == [(4, 3), (4, 3)]
    wire, shared = set(), 0
    for e in events:
        if e["tid"] in ("PUSH", "PULL"):
            wire.update(range(e["ts"], e["ts"] + e["dur"]))
    for lo, hi, number in rounds:
        d2h = set()
        for e in events:
            if e["tid"] == "D2H" and e["args"]["round"] == number:
                d2h.update(range(e["ts"], e["ts"] + e["dur"]))
        shared += sum(1 for t in d2h & wire if lo <= t < hi)
    assert shared == 4350
    assert reader(NAME)(_ctx(os.path.join(DATA, "stream"))) == pytest.approx(
        shared / len(rounds) / 1e3)


def test_a_round_queued_after_its_last_copy_reads_zero():
    """data/spans: the parent's shape of round, `D2H`s and all, and no
    `units_early`."""
    assert any(e["tid"] == "D2H" for e in _events("spans"))
    assert reader(NAME)(_ctx(os.path.join(DATA, "spans"))) == 0


@pytest.mark.parametrize("trace_dir", [os.path.join(DATA, "comm"), DATA],
                         ids=["no_round", "no_trace"])
def test_reader_says_nothing_without_a_round(trace_dir):
    assert reader(NAME)(_ctx(trace_dir)) is None
