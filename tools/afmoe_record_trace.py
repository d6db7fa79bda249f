"""Records the small trace that `benchmark/tests/test_afmoe.py` reads its
readers on: the afmoe cell at tiny widths, two expert layers (one sliding,
one full), five traced steps through the in-graph job, on whatever chip
JAX finds.

    python3 tools/afmoe_record_trace.py <cell> <out.xplane.pb>
"""

import dataclasses
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(cell_name: str, out: str) -> int:
    import jax

    from benchmark.harness import chip, manifest, measure
    from benchmark.reduce import xplane
    from benchmark.tests import tiny_afmoe
    cell = manifest.load_cell(cell_name)
    config = tiny_afmoe.config(layers=[4, 7])
    cell = dataclasses.replace(cell, config=config,
                               job={**cell.job, **config["job"]})
    line, _ = measure.run_cell(
        cell, seed=3, seconds=1.0, trace=True, devices=jax.devices()[:1],
        peaks=chip.require(jax.devices(), 1), t_start=0.0)
    print(line)
    shutil.copy(xplane.find(os.path.join(measure.TRACE_ROOT, cell.name)), out)
    return 0 if line["correct"] else 1


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
