"""BENCHMARK.json against what the harness needs of it: every name leads
to a file, every per-layer metric to a reader and to an end-to-end metric
its cells report."""

import json
import os
import re

import pytest

from benchmark.harness import chip, manifest, readers

with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_names_units_and_lengths():
    entries = (MANIFEST["configs"] + MANIFEST["workloads"]
               + MANIFEST["end_to_end"] + MANIFEST["per_layer"])
    names = [e["name"] for e in entries]
    assert all(NAME.match(n) for n in names), names
    for group in ("configs", "workloads"):
        got = [e["name"] for e in MANIFEST[group]]
        assert len(got) == len(set(got))
    metrics = [m["name"] for m in
               MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for e in MANIFEST["workloads"] + MANIFEST["configs"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    assert len(json.dumps(MANIFEST)) < 64 * 1024


@pytest.mark.parametrize("w", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_cell_resolves(w):
    cell = manifest.load_cell(w["name"])
    assert cell.chips == w["chips"] == cell.traffic["chips"]
    assert os.path.isfile(os.path.join(
        manifest.BENCH, "families", cell.config["family"] + ".py"))
    assert os.path.isfile(os.path.join(
        manifest.BENCH, "reference", cell.config["family"] + ".py"))
    assert os.path.isfile(os.path.join(
        manifest.BENCH, "jobs", cell.traffic["job"] + ".py"))
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e, (w["name"], m["name"])
        assert callable(readers.reader(m["name"]))


def test_four_chip_cells_are_a_quarter_at_most():
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(MANIFEST["workloads"]) // 4)


def test_no_cell_is_named_in_code():
    cells = [w["name"] for w in MANIFEST["workloads"]]
    for folder, _, files in os.walk(manifest.BENCH):
        if os.path.basename(folder) in ("tests", "__pycache__"):
            continue
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(folder, name)) as f:
                    text = f.read()
                assert not any(c in text for c in cells), (folder, name)


def test_peaks():
    v5e = chip.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["ici_bits_per_s"] == 1600e9
    with pytest.raises(SystemExit):
        chip.peaks_for("TPU v9 mystery")
    with pytest.raises(SystemExit):
        chip.peaks_for("source")
