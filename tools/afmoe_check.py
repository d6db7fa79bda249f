"""The afmoe cell's reference check alone, over seeds and over the broken
variants of the program, at the cell's own widths:

    python3 tools/afmoe_check.py --workload <cell> --seeds 11 12 13 \
        [--variants all | name ...] [--out chiprun_out/afmoe_check.jsonl]

One JSON line a comparison: what `benchmark/harness/correct.py` would put
under `detail.reference` in a run of the cell, whether it passes the
configuration's tolerances, what the reference saw of the program's choice
of experts and the program's own routing counters.  The variants
(`benchmark/tests/afmoe_variants.py`) are run on the first seed and each
has to fail.  This is how the tolerances in the configuration's
`reference_check` were measured (PERF.md, Findings, PR 29); it measures
no time, and runs wherever JAX does.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="*", default=[])
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import jax

    from benchmark.families import afmoe as families_afmoe
    from benchmark.harness import correct, manifest, seeded
    from benchmark.tests import afmoe_variants
    from byteps_tpu.models import afmoe
    from byteps_tpu.parallel import dropless_moe
    from byteps_tpu.utils import compile_cache
    compile_cache.enable()
    cell = manifest.load_cell(args.workload)
    family = families_afmoe.Family(cell.config, cell.job)
    names = (list(afmoe_variants.VARIANTS) if args.variants == ["all"]
             else args.variants)
    out = open(args.out, "a") if args.out else None

    def step_counters(params, seed):
        """The counters of the step's own batch, all its sequences routed
        together as the timed step routes them."""
        tokens = seeded.batch(family, seed, cell.job["per_chip_batch"])[0]
        routing = jax.jit(lambda p, t: afmoe.routing(p, t, family.cfg))(
            params, tokens)
        return jax.tree.map(
            lambda a: [float(x) for x in a],
            jax.vmap(lambda r: dropless_moe.counters(r, tokens.size))(
                routing))

    def compare(seed, variant):
        del family.selection[:], family.routing_counters[:]
        params = seeded.params(family, seed)
        got = correct.gradient_agreement(
            family.loss, family.reference_loss, params,
            seeded.batch(family, seed, family.reference_check["samples"]))
        jax.effects_barrier()
        line = {"seed": seed, "variant": variant,
                "device": jax.devices()[0].device_kind,
                "ok": correct.agreement_ok(got, family.reference_check),
                **got, "selection": list(family.selection),
                "routing_counters": list(family.routing_counters)}
        if variant is None:
            line["step_counters"] = step_counters(params, seed)
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()
        return line["ok"]

    ok = all([compare(seed, None) for seed in args.seeds])
    for name in names:
        with afmoe_variants.VARIANTS[name](family):
            ok = (not compare(args.seeds[0], name)) and ok
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
