"""Tier-1 duration budget gate (tools/check_test_budget.py + the
conftest recorder): a non-slow test over the per-test budget, a file
over the file budget and a total over its share of the driver's limit
each fail BY NAME, so the growing suite can't silently run into the
driver's timeout (ISSUE 15 satellite; the file and the total: PR 59,
after the clock cut PR 58's run)."""

import json
import os
import subprocess
import sys

import conftest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(ROOT, "tools")
if TOOLS not in sys.path:
    sys.path.insert(0, TOOLS)

import check_test_budget  # noqa: E402


def test_check_flags_only_nonslow_over_budget():
    durations = {
        "tests/test_a.py::fast": {"duration": 1.2, "slow": False},
        "tests/test_a.py::creeping": {"duration": 75.0, "slow": False},
        "tests/test_b.py::worse": {"duration": 120.0, "slow": False},
        "tests/test_b.py::chaos": {"duration": 300.0, "slow": True},
    }
    rep = check_test_budget.check(durations, budget_s=60.0)
    assert rep["slow_exempt"] == 1
    # Slowest first, slow-marked exempt, fast ones absent.
    assert [o["nodeid"] for o in rep["offenders"]] == [
        "tests/test_b.py::worse", "tests/test_a.py::creeping"]
    assert check_test_budget.check(durations, budget_s=500.0) \
        ["offenders"] == []


def _recording(**files):
    """`{file: [seconds of each of its tests]}` as a recording."""
    return {f"tests/{name}.py::test_{i}": {"duration": d, "slow": False}
            for name, durations in files.items()
            for i, d in enumerate(durations)}


def test_check_flags_a_file_no_test_of_which_is_over_budget():
    """One worker runs a whole file: eight tests of 50 s are 400 s of one
    worker's time, whatever the other five do."""
    durations = _recording(test_family=[50.0] * 8, test_other=[50.0] * 7)
    durations["tests/test_family.py::chaos"] = {"duration": 900.0,
                                                "slow": True}
    rep = check_test_budget.check(durations)
    assert not rep["offenders"] and not rep["total_over"]
    assert rep["files_over"] == [{"file": "tests/test_family.py",
                                  "duration": 400.0}]
    assert check_test_budget.over(rep)
    assert "tests/test_family.py  <-- FILE OVER BUDGET" in (
        check_test_budget.render(rep))
    del durations["tests/test_family.py::test_7"]
    rep = check_test_budget.check(durations)
    assert not check_test_budget.over(rep)
    assert "all within budget" in check_test_budget.render(rep)


def test_check_flags_a_total_no_file_of_which_is_over_budget():
    """The total's sixth is the wall time at best: thirty files of 240 s
    are 1,200 s a worker, over 80% of the driver's 1,470 s; PR 58's
    recording (7,560 s) was, and the clock cut its run."""
    assert check_test_budget.TOTAL_SHARE * check_test_budget.DRIVER_TIMEOUT_S \
        * check_test_budget.DRIVER_WORKERS == 7056.0
    assert str(int(check_test_budget.DRIVER_TIMEOUT_S)) in (
        check_test_budget.DRIVER_COMMAND)
    assert f"-n {check_test_budget.DRIVER_WORKERS} " in (
        check_test_budget.DRIVER_COMMAND)
    durations = _recording(**{f"test_{n}": [40.0] * 6 for n in range(30)})
    rep = check_test_budget.check(durations)
    assert not rep["offenders"] and not rep["files_over"]
    assert rep["total_s"] == 7200.0 and rep["total_over"]
    assert check_test_budget.over(rep)
    assert "TOTAL OVER 80%" in check_test_budget.render(rep)
    # a slow test counts for nothing, and 29 such files fit
    for nodeid in [n for n in durations if n.startswith("tests/test_0.py")]:
        durations[nodeid]["slow"] = True
    rep = check_test_budget.check(durations)
    assert rep["total_s"] == 6960.0 and not check_test_budget.over(rep)


def test_parse_pytest_durations_log():
    text = """
============================= slowest durations ==============================
12.34s call     tests/test_x.py::test_y
0.50s setup    tests/test_x.py::test_y
70.10s call     tests/test_z.py::test_big
0.01s teardown tests/test_z.py::test_big
=========================== short test summary info ===========================
"""
    got = check_test_budget.parse_durations_log(text)
    assert got == {
        "tests/test_x.py::test_y": {"duration": 12.34, "slow": False},
        "tests/test_z.py::test_big": {"duration": 70.1, "slow": False},
    }
    rep = check_test_budget.check(got, budget_s=60.0)
    assert [o["nodeid"] for o in rep["offenders"]] \
        == ["tests/test_z.py::test_big"]


def test_cli_paths(tmp_path):
    """No recording -> exit 0 (first run); a breaching recording ->
    exit 1 naming the test; a clean one -> exit 0."""
    tool = os.path.join(TOOLS, "check_test_budget.py")
    missing = str(tmp_path / "nope.json")
    r = subprocess.run([sys.executable, tool, missing],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0 and "nothing to check" in r.stdout

    rec = tmp_path / "durations.json"
    rec.write_text(json.dumps({"durations": {
        "tests/test_q.py::huge": {"duration": 200.0, "slow": False}}}))
    r = subprocess.run([sys.executable, tool, str(rec), "--json"],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 1
    assert json.loads(r.stdout)["offenders"][0]["nodeid"] \
        == "tests/test_q.py::huge"

    rec.write_text(json.dumps({"durations": {
        "tests/test_q.py::ok": {"duration": 2.0, "slow": False}}}))
    r = subprocess.run([sys.executable, tool, str(rec)],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0 and "within budget" in r.stdout


def test_previous_tier1_run_within_budget():
    """THE wired gate: the conftest recorder's last session must hold no
    non-slow test over the budget, no file over the file budget, and a
    total inside its share of the driver's limit.  A breach introduced by
    a PR fails here on the next tier-1 run, naming the culprit — before
    the driver's timeout ever fires.  First run on a clean checkout:
    vacuously green (no recording yet)."""
    durations = check_test_budget.load_recorded(conftest.DURATIONS_PATH)
    if durations is None:
        return      # nothing recorded yet — the next run is covered
    budget = float(os.environ.get("BYTEPS_TPU_TEST_BUDGET_S") or
                   check_test_budget.DEFAULT_BUDGET_S)
    rep = check_test_budget.check(durations, budget_s=budget)
    assert not check_test_budget.over(rep), check_test_budget.render(rep)
