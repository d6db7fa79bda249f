"""The reduction of the program's own stage spans, on a pair recorded
on the CPU (data/spans: three traced rounds of a four-leaf, 1.2 MB tree
through `bps.push_pull_tree` against a server child, under
`tracecap.capture`; `0/comm.json` is the program's merged trace and
`plugins/profile/tiny/tiny.xplane.pb` the profiler's), and on the older
recording that has no such span (data/comm)."""

import json
import os

import pytest

from benchmark.harness import tracecap
from benchmark.harness.readers import reader
from benchmark.reduce import program_spans, xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SPANS = os.path.join(DATA, "spans")
NEW = ("ps.span_round_ms", "ps.d2h_ms", "ps.stage_ms", "ps.wait_ms",
       "ps.h2d_ms", "ps.unspanned_ms", "ps.wire_busy_ms", "ps.exposed_ms",
       "ps.free_ms")
# The three `byteps.round` annotations of the recording, in nanoseconds
# on the profiler's clock.
ROUNDS_NS = ((125232, 6396164), (7302218, 12987221), (13063018, 19273563))
# Instructions of a chip that never ran: busy 0-1 ms, 6-8 ms, 19-20 ms.
OPS = [("%a = f32[] add()", 0, 1_000_000),
       ("%b = f32[] add()", 6_000_000, 8_000_000),
       ("%c = f32[] add()", 19_000_000, 20_000_000)]


def _ctx(trace_dir, ops=(), round_s=()):
    return tracecap.Context(
        trace=xplane.Trace(ops=[list(ops)] if ops else [], async_ops=[],
                           host=[]),
        n_steps=3, first_step=0, n_chips=1, samples_per_step=1, family=None,
        peaks={}, extras={"ps_round_s": list(round_s)}, dir=trace_dir)


def _events():
    with open(os.path.join(SPANS, "0", "comm.json")) as f:
        return [e for e in json.load(f)["traceEvents"] if e.get("ph") == "X"]


def test_rounds_of_the_recording():
    rounds = program_spans.rounds(SPANS)
    assert rounds.spans == ((3831446122, 3831446122 + 6268),
                            (3831453297, 3831453297 + 5684),
                            (3831459058, 3831459058 + 6209))
    # per stage, the three rounds' spans added up by hand
    assert rounds.stage_us == {
        "PACK": 1171 + 509 + 1087, "D2H": 20 + 20 + 23,
        "STAGE": 2428 + 2314 + 736, "WAIT": 533 + 16 + 1712,
        "H2D": 432 + 498 + 436, "SCATTER": 938 + 1708 + 1721,
        "FREE": 9 + 12 + 9}
    assert program_spans.annotations(SPANS) == ROUNDS_NS


# Hand-computed from the sums above: microseconds over three rounds.
@pytest.mark.parametrize("name,expected", [
    ("ps.span_round_ms", (6268 + 5684 + 6209) / 3 / 1e3),
    ("ps.d2h_ms", 63 / 3 / 1e3),
    ("ps.stage_ms", (2767 + 5478) / 3 / 1e3),
    ("ps.wait_ms", 2261 / 3 / 1e3),
    ("ps.h2d_ms", (1366 + 4367) / 3 / 1e3),
    ("ps.free_ms", 30 / 3 / 1e3),
    # the job's timer said 8 ms a round
    ("ps.unspanned_ms",
     8.0 - (63 + 2767 + 5478 + 2261 + 1366 + 4367 + 30) / 3e3),
    # idle 1-6 ms lies in the first round; idle 8-19 ms less the 75.797 us
    # between the second round's end and the third's start
    ("ps.exposed_ms", (5_000_000 + 11_000_000
                       - (13063018 - 12987221)) / 3 / 1e6),
])
def test_reader_gives_the_hand_computed_value(name, expected):
    ctx = _ctx(SPANS, ops=OPS, round_s=[0.008] * 3)
    assert reader(name)(ctx) == pytest.approx(expected, rel=1e-9)


def test_wire_busy_is_the_union_over_all_partitions():
    """Against a count of the microseconds some PUSH or PULL covers."""
    rounds = program_spans.rounds(SPANS)
    covered = set()
    for e in _events():
        if e["tid"] in ("PUSH", "PULL"):
            covered.update(range(e["ts"], e["ts"] + e["dur"]))
    inside = sum(1 for t in covered
                 if any(lo <= t < hi for lo, hi in rounds.spans))
    assert rounds.wire_busy_us == inside > 0
    got = reader("ps.wire_busy_ms")(_ctx(SPANS))
    assert got == pytest.approx(inside / 3 / 1e3)
    assert got <= reader("ps.span_round_ms")(_ctx(SPANS))


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("trace_dir", [os.path.join(DATA, "comm"), DATA],
                         ids=["no_stage_spans", "no_trace"])
def test_reader_says_nothing_of_an_older_trace(name, trace_dir):
    """data/comm/0/comm.json was written by a program without stage
    spans: no metric, not a zero.  The chip's instructions and the
    job's timer are there, as they are on the parent commit."""
    ctx = _ctx(trace_dir, ops=OPS, round_s=[0.008] * 3)
    assert reader(name)(ctx) is None


def test_a_stage_outside_any_round_is_not_counted():
    events = _events()
    stray = {"ph": "X", "pid": 0, "tid": "WAIT", "ts": 0, "dur": 10**9,
             "name": "x", "args": {"round": 0, "key": 1}}
    assert program_spans.reduce(events + [stray]) == program_spans.reduce(
        events)
    assert program_spans.reduce(
        [e for e in events if e["tid"] != "ROUND"]) is None
