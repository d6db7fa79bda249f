"""BENCHMARK.json, read as data: a cell names its configuration and its
traffic mix, each a file found by that name; a metric applies to a cell
unless it lists the cells it exists in.  No cell, configuration, mix or
metric is named in code."""

from __future__ import annotations

import dataclasses
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # benchmark/configs/<config>.json
    traffic: dict         # benchmark/traffic/<traffic>.json
    job: dict             # the configuration's job, the mix's keys on top
    end_to_end: tuple     # metric entries that exist in this cell
    per_layer: tuple


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: str = ROOT) -> Cell:
    manifest = _load(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"benchmark: no workload {name!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = _load(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load(os.path.join(root, "benchmark", "traffic",
                                 w["traffic"] + ".json"))
    if traffic["chips"] != w["chips"]:
        raise SystemExit(f"benchmark: {name} asks for {w['chips']} chip(s), "
                         f"its traffic mix for {traffic['chips']}")
    # The batch is the configuration's own job; a mix may set another.
    job = {**config["job"],
           **{k: traffic[k] for k in ("per_chip_batch", "seq_len")
              if k in traffic}}
    return Cell(
        name=name, chips=w["chips"], config=config, traffic=traffic, job=job,
        end_to_end=tuple(m for m in manifest["end_to_end"]
                         if _applies(m, name)),
        per_layer=tuple(m for m in manifest["per_layer"]
                        if _applies(m, name)))
