"""How an expert decoder's cell (afmoe, mellum) moves its routing while it
trains, step by step:

    python3 tools/afmoe_drift.py --workload <cell> --seeds 11 12 \
        [--inits 0.1 0.05] [--steps 32] [--every 3] [--tiny]

The cell trains on one batch, and only the held experts add to the result,
so the routers learn to send them more: the rows on the held experts rise
through a run, and the step's time with them (PERF.md, Findings, PR 29).
One JSON line a (seed, init): each step's milliseconds and loss (each step
waited for, so a little over the timed loop's), and every `--every` steps
the program's own counters of the step's batch under the weights of that
moment.  `--inits` starts `post_attn_ln` elsewhere than the configuration
does (afmoe's `post_attn_norm_init`; the mellum cell pins no such option);
only weights differ, so one compiled step serves them all.  This is how a
cell's `moe_capacity_factor` is chosen: over what `held_rows_per_token`
reaches inside a window.
"""

import argparse
import copy
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--inits", type=float, nargs="*", default=[])
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--every", type=int, default=3)
    ap.add_argument("--capacity", type=float,
                    help="another `moe_capacity_factor` than the cell pins")
    ap.add_argument("--tiny", action="store_true",
                    help="the cell at the widths of benchmark/tests")
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    import byteps_tpu as bps
    from benchmark.harness import manifest, seeded
    from byteps_tpu.parallel import dropless_moe
    from byteps_tpu.utils import compile_cache
    compile_cache.enable()
    if args.tiny:
        from benchmark.tests import (tiny, tiny_afmoe,  # noqa: F401
                                     tiny_keye, tiny_kimilinear,
                                     tiny_lfm2, tiny_mellum, tiny_nemotronh,
                                     tiny_sdarmoe)
        cell = tiny.tiny_cell(args.workload)
    else:
        cell = manifest.load_cell(args.workload)
    name = cell.config["family"]
    families = importlib.import_module(f"benchmark.families.{name}")
    # (a family's model module is called after it, but for three)
    model = importlib.import_module("byteps_tpu.models." + {
        "nemotronh": "nemotron_h", "kimilinear": "kimi_linear",
        "sdarmoe": "sdar"}.get(name, name))
    # sdar routes the two copies of a batch, whose noise is part of it:
    # its `routing` takes the batch whole and counts by the ROW
    two_copies = name == "sdarmoe"
    pinned = cell.config["program_options"]["pinned"]
    option = "post_attn_norm_init"
    if args.inits and option not in pinned:
        ap.error(f"the {name} cell pins no {option}")

    def family_at(init):
        config = copy.deepcopy(cell.config)
        if init is not None:
            config["program_options"]["pinned"][option] = init
        if args.capacity is not None:
            config["program_options"]["pinned"][
                "moe_capacity_factor"] = args.capacity
        return families.Family(config, cell.job)

    family = family_at(pinned.get(option))
    bps.init()
    mesh = bps.make_mesh(devices=jax.devices()[:1])
    opt = bps.DistributedOptimizer(family.optimizer())
    step = bps.build_train_step(family.loss, opt, mesh, donate=True)
    opt_init = jax.jit(opt.init)
    n = cell.job["per_chip_batch"]

    @jax.jit
    def counters(params, batch):
        tokens = batch[0]
        routing = model.routing(params, batch if two_copies else tokens,
                                family.cfg)
        rows = tokens.size * (2 if two_copies else 1)
        return jax.vmap(
            lambda r: dropless_moe.counters(r, rows))(routing)

    out = open(args.out, "a") if args.out else None
    for init in args.inits or [pinned.get(option)]:
        for seed in args.seeds:
            params = seeded.params(family_at(init), seed)
            opt_state = opt_init(params)
            batch = jax.device_put(
                seeded.batch(family, seed, n),
                NamedSharding(mesh, PartitionSpec("dp")))
            line = {"seed": seed, option: init,
                    "moe_capacity_factor": family.cfg.moe_capacity_factor,
                    "device": jax.devices()[0].device_kind,
                    "step_ms": [], "loss": [], "counters": {}}
            for i in range(args.steps):
                if i % args.every == 0:
                    line["counters"][i] = jax.tree.map(
                        lambda a: [round(float(x), 4) for x in a],
                        counters(params, batch))
                t0 = time.perf_counter()
                params, opt_state, loss = step(params, opt_state, batch)
                line["loss"].append(round(float(loss), 4))
                line["step_ms"].append(
                    round((time.perf_counter() - t0) * 1e3, 1))
            del params, opt_state
            print(json.dumps(line), flush=True)
            if out:
                out.write(json.dumps(line) + "\n")
                out.flush()
    bps.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
