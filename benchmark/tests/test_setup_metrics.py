"""The eight `setup.*` readers on a recorded compile log
(`data/compile_log.json`, as `bps.get_compile_log()` returns it: a trace
on a second thread inside another's, a compile that overlaps another's,
one `uncached` program, the train step compiled twice, and two records
after `steady_at`), and on a program that keeps no such log."""

import json
import os

import pytest

import byteps_tpu as bps
from benchmark.harness import manifest, readers

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "compile_log.json")
EXPECTED = {
    "setup.before_programs_s": 12.0,    # 1000 to the first trace at 1012
    "setup.trace_s": 11.0,              # 4 (the helper's 2 inside) + 4 + 3
    "setup.lower_s": 4.0,
    "setup.compile_s": 47.0,            # 10 + 21 (20 and 2 overlap by 1) + 16
    "setup.programs": 5.0,
    "setup.cold_programs": 2.0,         # `one` uncached, the step's miss
    "setup.step_s": 26.0,               # 1060-1076 and 1080-1090
    "setup.step_compiles": 2.0,
}


@pytest.fixture
def recorded():
    with open(DATA) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_the_recorded_log(name, recorded, monkeypatch):
    monkeypatch.setattr(bps, "get_compile_log", lambda: recorded,
                        raising=False)
    assert readers.reader(name)(None) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_says_nothing_of_a_program_without_the_log(name, monkeypatch):
    """As the parent of the PR that brought the log is: no such name."""
    monkeypatch.delitem(bps._HOME, "get_compile_log")
    monkeypatch.delattr(bps, "get_compile_log", raising=False)
    assert not hasattr(bps, "get_compile_log")
    assert readers.reader(name)(None) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_says_nothing_where_setup_never_ended(name, recorded,
                                                     monkeypatch):
    recorded["steady_at"] = None
    monkeypatch.setattr(bps, "get_compile_log", lambda: recorded,
                        raising=False)
    assert readers.reader(name)(None) is None


def test_the_manifest_lists_them_as_the_table_has_them():
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        listed = json.load(f)
    cells = [w["name"] for w in listed["workloads"]]
    ingraph = [c for c in cells if ".ingraph-" in c]
    mine = {m["name"]: m for m in listed["per_layer"]
            if m["moves"] == "setup_s"}
    assert set(mine) == set(EXPECTED)
    for name, m in mine.items():
        assert (m["layer"], m["better"]) == ("entry / trainer", "lower")
        assert m["workloads"] == (
            ingraph if name.startswith("setup.step_") else cells)
