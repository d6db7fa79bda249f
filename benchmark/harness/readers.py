"""Per-layer metrics, each read by a file of its own:
`benchmark/layer_metrics/<metric name>.py` with one function
`read(ctx) -> number or None`, where `ctx` is a `tracecap.Context`.  A
reader that finds nothing to read returns None and the metric is left
out of the line.  A metric is added by adding its file and its entry in
BENCHMARK.json; no list of names is kept in code."""

from __future__ import annotations

import importlib.util
import os

from benchmark.harness import manifest

_DIR = os.path.join(manifest.BENCH, "layer_metrics")


def reader(name: str):
    """The `read` function of the metric called `name`."""
    path = os.path.join(_DIR, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: per-layer metric {name!r} has no "
                         f"reader {path}")
    spec = importlib.util.spec_from_file_location(
        "benchmark.layer_metrics." + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def read_all(entries, ctx) -> dict:
    """`{name: {"value", "unit"}}` for the manifest's per-layer `entries`
    whose readers found something."""
    out = {}
    for entry in entries:
        value = reader(entry["name"])(ctx)
        if value is not None:
            out[entry["name"]] = {"value": float(value),
                                  "unit": entry["unit"]}
    return out
