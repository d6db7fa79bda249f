"""The benchmark's one command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

run from the root of a checkout on a machine that holds the cell's chips.
Everything about the cell is data: BENCHMARK.json names its configuration
(benchmark/configs/), its traffic mix (benchmark/traffic/) and its
metrics (benchmark/layer_metrics/); the configuration names its family
(benchmark/families/, with the plain reference in benchmark/reference/)
and the mix its job (benchmark/jobs/).

The last line of standard output is the result, as the contract has it.
Without an accelerator of a kind the peak table knows, with fewer chips
than the cell asks for, or outside a checkout of the repo, the command
ends non-zero and prints no result.
"""

import time

T_START = time.perf_counter()       # set-up counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# The checkout, and not this directory, is where imports start: the
# directory's own names (`tests`, `reduce`) must shadow nobody's.
sys.path[0] = ROOT


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    # A kill at the time limit must still reach the `finally`s that stop
    # the server child.
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))

    from benchmark.harness import manifest
    cell = manifest.load_cell(args.workload)

    import jax

    from benchmark.harness import chip, measure
    from byteps_tpu.utils import compile_cache
    peaks = chip.require(jax.devices(), cell.chips)
    # JAX_COMPILATION_CACHE_DIR where it is set, else <checkout>/.jax_cache
    compile_cache.enable()
    line, detail = measure.run_cell(
        cell, args.seed, args.seconds, bool(args.trace),
        jax.devices()[:cell.chips], peaks, T_START)
    print(json.dumps({"detail": detail}), flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
