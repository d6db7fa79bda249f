"""`ps.round_faults` on recordings made on the CPU: data/faults (four
traced rounds of a five-leaf, 3.6 MB tree through `bps.push_pull_tree`
against a server child, by a program whose `ROUND` counts the process's
minor page faults and which keeps the memory it frees), data/stream and
data/spans (programs whose `ROUND` has no such count) and data/comm (a
program without stage spans)."""

import json
import os

import pytest

from benchmark.harness.readers import reader
from benchmark.tests.test_d2h_hidden import DATA, _ctx

NAME = "ps.round_faults"


def test_mean_of_the_recorded_rounds():
    """The four ROUNDs of data/faults count 6481, 586, 1172 and 0: the
    first fills the heap, the others find most of it there."""
    with open(os.path.join(DATA, "faults", "0", "comm.json")) as f:
        rounds = [e["args"] for e in json.load(f)["traceEvents"]
                  if e.get("tid") == "ROUND"]
    assert [a["minflt"] for a in rounds] == [6481, 586, 1172, 0]
    assert reader(NAME)(_ctx(os.path.join(DATA, "faults"))) == pytest.approx(
        (6481 + 586 + 1172 + 0) / 4, rel=1e-12)


@pytest.mark.parametrize("trace_dir", [
    os.path.join(DATA, "stream"), os.path.join(DATA, "spans"),
    os.path.join(DATA, "comm"), DATA],
    ids=["streamed_no_count", "spans_no_count", "no_round", "no_trace"])
def test_reader_says_nothing_without_a_count(trace_dir):
    """The parent's ROUND has no `minflt`: no metric, not a zero."""
    assert reader(NAME)(_ctx(trace_dir)) is None
