"""Each job at tiny size on the CPU mesh, through the function run.py
calls, and the shape of the result line; run.py itself off the chip."""

import json
import os
import subprocess
import sys

import jax
import pytest

from benchmark.harness import manifest, measure
from benchmark.tests.tiny import tiny_cell

PEAKS = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}  # no chip's


def _cells():
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("trace", [False, True], ids=["timed", "traced"])
@pytest.mark.parametrize("name", _cells())
def test_cell_at_tiny_size(name, trace):
    cell = tiny_cell(name)
    line, detail = measure.run_cell(
        cell, seed=3, seconds=0.5, trace=trace,
        devices=jax.devices()[:cell.chips], peaks=PEAKS, t_start=0.0)
    assert detail["checks"]["no_compile_in_window"], detail
    assert line["correct"] is True, detail
    assert line["failed"] == 0 and line["attempted"] == detail["steps"] >= 1
    assert set(line) - {"breakdown"} == {"correct", "attempted", "failed",
                                         "metrics", "device"}
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])
    assert line["device"]["count"] == cell.chips
    listed = cell.per_layer if trace else cell.end_to_end
    units = {m["name"]: m["unit"] for m in listed}
    for metric, got in line["metrics"].items():
        assert got["unit"] == units[metric]
        assert isinstance(got["value"], float)
    if trace:
        # No device plane on the CPU: readers of the device trace find
        # nothing and say nothing; what the program itself spans is read.
        assert all(m["source"] != "device_trace" for m in cell.per_layer
                   if m["name"] in line["metrics"])
        assert "busy_s" not in line["device"]
    else:
        assert set(line["metrics"]) == set(units)
        assert all(v["value"] > 0 for v in line["metrics"].values())
    if cell.traffic["job"] == "ps_joint":
        assert detail["checks"]["pulled_equals_pushed.before"] is True
        assert detail["checks"]["pulled_equals_pushed.after"] is True
        assert detail["checks"]["server_off_the_accelerator.after"] is True
        if trace:
            assert {"ps.round_ms", "ps.wire_ms", "ps.server_ms",
                    "ps.offwire_ms"} <= set(line["metrics"])
    if cell.chips > 1 or cell.traffic["job"] == "ps_joint":
        assert detail["plain_step"]["ok"], detail["plain_step"]


@pytest.mark.parametrize("alone", [False, True],
                         ids=["cpu_platform", "benchmark_alone"])
def test_run_py_refuses(tmp_path, alone):
    """Under the CPU platform, and in a directory that holds only
    BENCHMARK.json and benchmark/, the command ends non-zero and prints
    no result line."""
    from byteps_tpu.utils.hermetic import cpu_subprocess_env
    env = cpu_subprocess_env()
    cwd = manifest.ROOT
    if alone:
        import shutil
        shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), tmp_path)
        shutil.copytree(manifest.BENCH, tmp_path / "benchmark",
                        ignore=shutil.ignore_patterns("__pycache__"))
        cwd = str(tmp_path)
        env.pop("PYTHONPATH", None)
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", _cells()[0],
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        env=env, cwd=cwd, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
    if not alone:
        assert "no accelerator" in r.stderr
