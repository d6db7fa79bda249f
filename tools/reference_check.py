"""A cell's reference check alone, over seeds and over the broken variants
of its program, at the cell's own widths:

    python3 tools/reference_check.py --workload <cell> --seeds 11 12 13 \
        [--variants all | name ...] [--only-variants] [--leaves] \
        [--out chiprun_out/reference_check.jsonl]

One JSON line a comparison: what `benchmark/harness/correct.py` would put
under `detail.reference` in a run of the cell, whether it passes the
configuration's tolerances, the seconds it took and what the family keeps
beside the three numbers (`EXTRAS` below).  The family is the one the
cell's configuration names (`benchmark/families/<family>.py`), its
variants `benchmark/tests/<family>_variants.py`; they are run on the
first seed and each has to fail.  This is how the tolerances in a
configuration's `reference_check` were measured (PERF.md, Findings, PR 29
and PR 34); it measures no time of the program's, and runs wherever JAX
does.  (Once two tools, `afmoe_check.py` and `granite_check.py`, which
older notes name.)

    python3 tools/reference_check.py --workload <cell> --record <out.pb>

records instead the small trace that the family's readers are tested on
(`RECORD` below: the granitehybrid, mellum, keye, nemotronh and joyai
cells).
"""

import argparse
import dataclasses
import importlib
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


# a family's model module where it is not called after the family
MODELS = {"nemotronh": "nemotron_h", "kimilinear": "kimi_linear",
          "sdarmoe": "sdar"}
# families whose layers run TWO copies of a batch `(tokens, masked,
# weight)`: `routing` takes the batch whole, and counts by the row
TWO_COPIES = {"sdarmoe"}


def _expert_extras(family, cell, params, seed, variant):
    """What the reference saw of the program's choice of experts, the
    program's own routing counters and, for the program as it is, the
    counters of the step's own batch, all its sequences routed together
    as the timed step routes them.  For the families of expert decoders,
    whose model module has the family's name."""
    import jax

    from benchmark.harness import seeded
    from byteps_tpu.parallel import dropless_moe
    name = cell.config["family"]
    model = importlib.import_module(
        f"byteps_tpu.models.{MODELS.get(name, name)}")
    out = {"selection": list(family.selection),
           "routing_counters": list(family.routing_counters)}
    del family.selection[:], family.routing_counters[:]
    if variant is None:
        batch = seeded.batch(family, seed, cell.job["per_chip_batch"])
        whole = name in TWO_COPIES
        routing = jax.jit(lambda p, b: model.routing(
            p, b if whole else b[0], family.cfg))(params, batch)
        rows = batch[0].size * (2 if whole else 1)
        out["step_counters"] = jax.tree.map(
            lambda a: [float(x) for x in a],
            jax.vmap(lambda r: dropless_moe.counters(r, rows))(routing))
        if whole:
            out["noise"] = jax.tree.map(float, model.batch_counters(batch))
    return out


def _keye_extras(family, cell, params, seed, variant):
    """The expert decoders' extras and, for the program as it is,
    `select_passes`: a layer's blocks of rows of `index_topk` by the
    passes over their slab of scores they ran, the longer count where a
    block had a tie at a threshold to break."""
    import jax
    import numpy as np

    from benchmark.harness import seeded
    from byteps_tpu.models import keye
    from byteps_tpu.ops import sparse_attention
    out = _expert_extras(family, cell, params, seed, variant)
    if variant is None and family.cfg.attn_impl == "flash":
        tokens = seeded.batch(family, seed, 1)[0]
        passes = jax.jit(lambda p, t: keye.select_passes(
            p, t, family.cfg, family._streams(t)))(params, tokens)
        rows = sparse_attention.select_rows(tokens.shape[1])
        out["select_passes"] = [
            {int(n): int(blocks) for n, blocks in zip(
                *np.unique(layer[0, ::rows], return_counts=True))}
            for layer in jax.device_get(passes)]
    return out


def _granitehybrid_extras(family, cell, params, seed, variant):
    """The scan alone in float32 against the recurrence, the number that
    reaches `correct` through the loss, a sample of the check."""
    import jax

    from benchmark.harness import seeded
    tokens = seeded.batch(family, seed, family.reference_check["samples"])[0]
    scan = jax.jit(lambda p, t: family.scan_disagreement(p, t))
    return {"scan_rel_diff": [float(scan(params, tokens[i:i + 1]))
                              for i in range(tokens.shape[0])]}


def _granitehybrid_record(cell, out: str) -> int:
    """`benchmark/tests/data/tiny_granitehybrid.xplane.pb`: the cell at
    tiny widths, three layers (mamba, attention, mamba) and chunks of 128,
    five traced steps through the in-graph job."""
    import jax

    from benchmark.harness import chip, measure
    from benchmark.reduce import xplane
    from benchmark.tests import tiny_granitehybrid
    config = tiny_granitehybrid.config(layers=[4, 5, 6])
    # a chunk the chip's tiling takes: a block's last dimension is the
    # array's own or a multiple of 128
    config["published"]["mamba_chunk_size"] = 128
    cell = dataclasses.replace(cell, config=config,
                               job={**cell.job, **config["job"]})
    line, _ = measure.run_cell(
        cell, seed=3, seconds=1.0, trace=True, devices=jax.devices()[:1],
        peaks=chip.require(jax.devices(), 1), t_start=0.0)
    print(line)
    shutil.copy(xplane.find(os.path.join(measure.TRACE_ROOT, cell.name)), out)
    return 0 if line["correct"] else 1


def _mellum_record(cell, out: str) -> int:
    """`benchmark/tests/data/tiny_mellum.xplane.pb`: the cell at tiny
    widths but with heads of 128 (the chip's lane width, as the streaming
    kernels' tiles need), two layers (sliding, full), one sequence of
    1,024 positions under a window of 256 in tiles of 128, with the
    streaming kernels asked for by hand (a head's K and V are far inside
    the resident budget at this length), five traced steps through the
    in-graph job."""
    from unittest import mock

    import jax

    from benchmark.harness import chip, measure
    from benchmark.reduce import xplane
    from benchmark.tests import tiny_mellum
    from byteps_tpu.ops import flash_attention
    config = tiny_mellum.config(layers=[2, 3])
    config["published"].update(head_dim=128, sliding_window=256)
    config["job"].update(per_chip_batch=1, seq_len=1024)
    config["program_options"]["left_at_rule"].update(attn_block=128,
                                                     attn_block_k=128)
    cell = dataclasses.replace(cell, config=config,
                               job={**cell.job, **config["job"]})
    with mock.patch.object(flash_attention, "_use_streaming",
                           lambda *_: True):
        line, _ = measure.run_cell(
            cell, seed=3, seconds=1.0, trace=True, devices=jax.devices()[:1],
            peaks=chip.require(jax.devices(), 1), t_start=0.0)
    print(line)
    shutil.copy(xplane.find(os.path.join(measure.TRACE_ROOT, cell.name)), out)
    return 0 if line["correct"] else 1


def _keye_record(cell, out: str) -> int:
    """`benchmark/tests/data/tiny_keye.xplane.pb`: the cell at tiny widths
    but with heads of 128 and indexer heads of 64 (the published sizes:
    the chip's lane width), two layers, one sequence of 1,024 positions of
    which a row selects 256, in tiles of 128 x 128, five traced steps
    through the in-graph job."""
    import jax

    from benchmark.harness import chip, measure
    from benchmark.reduce import xplane
    from benchmark.tests import tiny_keye
    config = tiny_keye.config(layers=[0, 1])
    config["published"].update(
        head_dim=128,
        rope_scaling={"mrope_section": [16, 24, 24], "rope_type": "default",
                      "type": "default"},
        sa_config={"indexer_head_dim": 64, "indexer_num_heads": 4,
                   "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                   "q_chunk_size": 512, "topk": 256})
    config["job"].update(per_chip_batch=1, seq_len=1024)
    config["program_options"]["left_at_rule"].update(attn_block=128,
                                                     attn_block_k=128)
    cell = dataclasses.replace(cell, config=config,
                               job={**cell.job, **config["job"]})
    line, _ = measure.run_cell(
        cell, seed=3, seconds=1.0, trace=True, devices=jax.devices()[:1],
        peaks=chip.require(jax.devices(), 1), t_start=0.0)
    print(line)
    shutil.copy(xplane.find(os.path.join(measure.TRACE_ROOT, cell.name)), out)
    return 0 if line["correct"] else 1


def _nemotronh_record(cell, out: str) -> int:
    """`benchmark/tests/data/tiny_nemotronh.xplane.pb`: the cell at tiny
    widths but with what the chip's tiles ask (hidden 128, experts of 64
    = HALF a lane tile, so the grouped kernels take a width no multiple
    of 128, the shared expert 128; 16 mixer heads in 2 groups, chunks of
    128), three layers (M, *, E), one sequence of 1,024 positions with
    the streaming flash kernels asked for by hand, five traced steps
    through the in-graph job."""
    from unittest import mock

    import jax

    from benchmark.harness import chip, measure
    from benchmark.reduce import xplane
    from benchmark.tests import tiny_nemotronh
    from byteps_tpu.ops import flash_attention
    config = tiny_nemotronh.config(layers=[4, 5, 6])
    config["published"].update(tiny_nemotronh.ON_THE_CHIP)
    config["job"].update(per_chip_batch=1, seq_len=1024)
    cell = dataclasses.replace(cell, config=config,
                               job={**cell.job, **config["job"]})
    with mock.patch.object(flash_attention, "_use_streaming",
                           lambda *_: True):
        line, _ = measure.run_cell(
            cell, seed=3, seconds=1.0, trace=True, devices=jax.devices()[:1],
            peaks=chip.require(jax.devices(), 1), t_start=0.0)
    print(line)
    shutil.copy(xplane.find(os.path.join(measure.TRACE_ROOT, cell.name)), out)
    return 0 if line["correct"] else 1


def _joyai_record(cell, out: str) -> int:
    """`benchmark/tests/data/tiny_joyai.xplane.pb`: the cell at tiny widths
    but with the published head (queries and keys 128 + 64 wide, values
    128: the widths the kernels' names carry) and what else the chip's
    tiles ask (`tiny_joyai.ON_THE_CHIP`), the dense layer, one expert
    layer and the prediction module, one sequence of 1,024 positions in
    tiles of 128 with the streaming flash kernels asked for by hand, five
    traced steps through the in-graph job."""
    from unittest import mock

    import jax

    from benchmark.harness import chip, measure
    from benchmark.reduce import xplane
    from benchmark.tests import tiny_joyai
    from byteps_tpu.ops import flash_attention
    config = tiny_joyai.config(layers=[0, 1])
    config["published"].update(tiny_joyai.ON_THE_CHIP)
    config["job"].update(per_chip_batch=1, seq_len=1024)
    config["program_options"]["pinned"]["attn_impl"] = "flash"
    config["program_options"]["left_at_rule"].update(attn_block=128,
                                                     attn_block_k=128)
    cell = dataclasses.replace(cell, config=config,
                               job={**cell.job, **config["job"]})
    with mock.patch.object(flash_attention, "_use_streaming",
                           lambda *_: True):
        line, _ = measure.run_cell(
            cell, seed=3, seconds=1.0, trace=True, devices=jax.devices()[:1],
            peaks=chip.require(jax.devices(), 1), t_start=0.0)
    print(line)
    shutil.copy(xplane.find(os.path.join(measure.TRACE_ROOT, cell.name)), out)
    return 0 if line["correct"] else 1


def _ouro_extras(family, cell, params, seed, variant):
    """What the two parts compared alone read (`benchmark/families/ouro.py`
    `parts_disagreement`) and the exit gauges the check's batch set."""
    import byteps_tpu as bps
    out = {"parts": list(family.selection),
           "exit": {k: v for k, v in bps.get_metrics().items()
                    if k.startswith(("bps_exit_", "bps_loop_"))}}
    del family.selection[:]
    return out


EXTRAS = {"afmoe": _expert_extras, "mellum": _expert_extras,
          "keye": _keye_extras, "nemotronh": _expert_extras,
          "joyai": _expert_extras, "lfm2": _expert_extras,
          "kimilinear": _expert_extras, "sdarmoe": _expert_extras,
          "granitehybrid": _granitehybrid_extras, "ouro": _ouro_extras}
RECORD = {"granitehybrid": _granitehybrid_record, "mellum": _mellum_record,
          "keye": _keye_record, "nemotronh": _nemotronh_record,
          "joyai": _joyai_record}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--variants", nargs="*", default=[])
    ap.add_argument("--only-variants", action="store_true",
                    help="skip the seeds' own comparisons: the variants "
                         "alone, on the first seed (a process a variant "
                         "where the chip's memory is short)")
    ap.add_argument("--out")
    ap.add_argument("--leaves", action="store_true",
                    help="every leaf's [difference, norm ratio] too (the "
                         "gradients are computed a second time for it)")
    ap.add_argument("--record")
    args = ap.parse_args(argv)

    import jax

    from benchmark.harness import correct, manifest, seeded
    from byteps_tpu.utils import compile_cache
    compile_cache.enable()
    cell = manifest.load_cell(args.workload)
    name = cell.config["family"]
    if args.record:
        if name not in RECORD:
            ap.error(f"no recorded trace for the {name} family")
        return RECORD[name](cell, args.record)
    family = importlib.import_module(
        f"benchmark.families.{name}").Family(cell.config, cell.job)
    variants = {}
    if args.variants:
        module = importlib.import_module(f"benchmark.tests.{name}_variants")
        # a family's controls of its precision limits, where it has them
        variants = {**module.VARIANTS, **getattr(module, "CONTROLS", {})}
    names = list(variants) if args.variants == ["all"] else args.variants
    samples = family.reference_check["samples"]
    out = open(args.out, "a") if args.out else None

    def leaves(params, batch):
        mine = jax.jit(jax.grad(family.loss))(params, batch)
        ref = correct.reference_value_and_grad(family.reference_loss, params,
                                               batch)[1]
        both = jax.jit(lambda g, r: jax.tree.map(
            correct._rel_diff_and_norm_ratio, g, r))(mine, ref)
        return {jax.tree_util.keystr(path): [round(float(x), 5) for x in v]
                for path, v in jax.tree_util.tree_flatten_with_path(both)[0]}

    def compare(seed, variant):
        t0 = time.perf_counter()
        params = seeded.params(family, seed)
        batch = seeded.batch(family, seed, samples)
        got = correct.gradient_agreement(family.loss, family.reference_loss,
                                         params, batch)
        jax.effects_barrier()
        line = {"seed": seed, "variant": variant,
                "device": jax.devices()[0].device_kind,
                "ok": correct.agreement_ok(got, family.reference_check),
                **got, "seconds": time.perf_counter() - t0}
        if name in EXTRAS:
            line.update(EXTRAS[name](family, cell, params, seed, variant))
        if args.leaves:
            line["leaves"] = leaves(params, batch)
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()
        return line["ok"]

    ok = args.only_variants or all(
        [compare(seed, None) for seed in args.seeds])
    for variant in names:
        with variants[variant](family):
            ok = (not compare(args.seeds[0], variant)) and ok
    # Whether this call's set-up was warm: programs by the cache's answer,
    # seconds by stage.
    print(json.dumps({"compile_log": compile_cache.summary()}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
