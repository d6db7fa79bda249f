"""The scope map of the compiled step (`bps.get_step_scopes()`,
`common/devprof.py`): the parser on every family's tiny train step
compiled for a described TPU v5e, as `test_tpu_aot_compile.py` compiles
(no chip attached), and the record `build_train_step` keeps for it.

Nothing runs on the described chip.  The record's cases run tiny steps on
the CPU.
"""

import contextlib
import copy
import dataclasses
import functools
import logging
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else it logs under /tmp

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import byteps_tpu as bps
from benchmark.harness import measure
from benchmark.tests import (tiny, tiny_afmoe, tiny_granitehybrid,  # noqa: F401
                             tiny_joyai,
                             tiny_keye, tiny_kimilinear, tiny_lfm2,
                             tiny_mellum,               # join `tiny`'s table
                             tiny_nemotronh, tiny_ouro, tiny_sdarmoe)
from byteps_tpu.common import devprof
from byteps_tpu.ops import flash_attention as fa
from byteps_tpu.ops import ssd

# family -> (its tiny cell, the scopes its step must show, whether a
# layer is checkpointed)
FAMILIES = {
    "gpt2": ("gpt2-medium.ingraph-1chip",
             {"transformer.embed", "transformer.attn", "transformer.attn/qkv",
              "transformer.attn/out", "transformer.mlp", "transformer.head",
              "byteps.optimizer"}, True),
    "vgg": ("vgg16.ingraph-1chip",
            {"cnn.features", "cnn.classifier", "cnn.head",
             "byteps.optimizer"}, False),
    "afmoe": ("trinity-mini.ingraph-1chip",
              {"afmoe.embed", "afmoe.attn.sliding_attention",
               "afmoe.attn.full_attention", "afmoe.attn.full_attention/qkv",
               "afmoe.attn.sliding_attention/out", "afmoe.mlp", "afmoe.moe",
               "afmoe.moe/route", "afmoe.moe/gather", "afmoe.moe/grouped",
               "afmoe.moe/scatter", "afmoe.moe/exact", "afmoe.moe/shared",
               "afmoe.head", "byteps.optimizer"}, True),
    "granitehybrid": ("granite-4.0-h-micro.ingraph-1chip",
                      {"granite.embed", "granite.mamba.in_proj",
                       "granite.mamba.conv", "granite.mamba.scan",
                       "granite.mamba.gate_norm", "granite.mamba.out_proj",
                       "granite.attn", "granite.attn/qkv", "granite.attn/out",
                       "granite.mlp", "granite.head", "byteps.optimizer"},
                      True),
    "mellum": ("mellum2-12b-a2.5b-instruct.ingraph-1chip",
               {"mellum.embed", "mellum.attn.sliding_attention",
                "mellum.attn.full_attention", "mellum.attn.full_attention/qkv",
                "mellum.attn.sliding_attention/out", "mellum.moe",
                "mellum.moe/route", "mellum.moe/gather", "mellum.moe/grouped",
                "mellum.moe/scatter", "mellum.moe/exact", "mellum.head",
                "byteps.optimizer"}, True),
    "keye": ("keye-vl-2.0-30b-a3b.ingraph-1chip",
             {"keye.embed", "keye.attn.full_attention",
              "keye.attn.full_attention/qkv", "keye.attn.full_attention/index",
              "keye.attn.full_attention/select",
              "keye.attn.full_attention/sparse",
              "keye.attn.full_attention/out", "keye.moe", "keye.moe/route",
              "keye.moe/gather", "keye.moe/grouped", "keye.moe/scatter",
              "keye.moe/exact", "keye.head", "byteps.optimizer"}, True),
    "nemotronh": ("nemotron-labs-twotower-30b-a3b-base.ingraph-1chip",
                  {"nemotronh.embed", "nemotronh.mamba.in_proj",
                   "nemotronh.mamba.conv", "nemotronh.mamba.scan",
                   "nemotronh.mamba.gate_norm", "nemotronh.mamba.out_proj",
                   "nemotronh.attn", "nemotronh.attn/qkv",
                   "nemotronh.attn/out", "nemotronh.moe",
                   "nemotronh.moe/route", "nemotronh.moe/gather",
                   "nemotronh.moe/grouped", "nemotronh.moe/scatter",
                   "nemotronh.moe/exact", "nemotronh.moe/shared",
                   "nemotronh.head", "byteps.optimizer"}, True),
    # the prediction module's layer opens the layer's scopes UNDER its own
    "joyai": ("joyai-llm-flash.ingraph-1chip",
              {"joyai.embed", "joyai.attn", "joyai.attn/qkv",
               "joyai.attn/out", "joyai.dense", "joyai.moe",
               "joyai.moe/route", "joyai.moe/gather", "joyai.moe/grouped",
               "joyai.moe/scatter", "joyai.moe/exact", "joyai.moe/shared",
               "joyai.mtp", "joyai.mtp/joyai.attn",
               "joyai.mtp/joyai.attn/qkv", "joyai.mtp/joyai.attn/out",
               "joyai.mtp/joyai.moe/grouped", "joyai.mtp/joyai.moe/shared",
               "joyai.head", "byteps.optimizer"}, True),
    "lfm2": ("lfm2-24b-a2b.ingraph-1chip",
             {"lfm2.embed", "lfm2.conv.in_proj", "lfm2.conv.gate_conv",
              "lfm2.conv.out_proj", "lfm2.attn", "lfm2.attn/qkv",
              "lfm2.attn/out", "lfm2.dense", "lfm2.moe", "lfm2.moe/route",
              "lfm2.moe/gather", "lfm2.moe/grouped", "lfm2.moe/scatter",
              "lfm2.moe/exact", "lfm2.head", "byteps.optimizer"}, True),
    "kimilinear": ("kimi-linear-48b-a3b-instruct.ingraph-1chip",
                   {"kimi.embed", "kimi.kda.proj", "kimi.kda.conv",
                    "kimi.kda.gates", "kimi.kda.scan", "kimi.kda.gate_norm",
                    "kimi.kda.out_proj", "kimi.attn", "kimi.attn/qkv",
                    "kimi.attn/out", "kimi.dense", "kimi.moe",
                    "kimi.moe/route", "kimi.moe/gather", "kimi.moe/grouped",
                    "kimi.moe/scatter", "kimi.moe/exact", "kimi.moe/shared",
                    "kimi.head", "byteps.optimizer"}, True),
    "sdarmoe": ("sdar-30b-a3b-chat.ingraph-1chip",
                {"sdar.noise", "sdar.attn.block_diffusion",
                 "sdar.attn.block_diffusion/qkv",
                 "sdar.attn.block_diffusion/out", "sdar.moe",
                 "sdar.moe/route", "sdar.moe/gather", "sdar.moe/grouped",
                 "sdar.moe/scatter", "sdar.moe/exact", "sdar.head",
                 "byteps.optimizer"}, True),
    "ouro": ("ouro-2.6b.ingraph-1chip",
             {"ouro.embed", "ouro.attn.full_attention",
              "ouro.attn.full_attention/qkv", "ouro.attn.full_attention/out",
              "ouro.attn.full_attention/post_norm", "ouro.mlp",
              "ouro.mlp/post_norm", "ouro.exit", "ouro.head",
              "byteps.optimizer"}, True),
}
# Where a family's scopes start with another word than its name.
SCOPE_PREFIX = {"kimilinear": "kimi", "sdarmoe": "sdar"}
# The names the device trace was read by before this map: an unnamed
# kernel call is called after the innermost scope around it.  The expert
# layer's grouped products are the program's own kernels since PR 40,
# named `ragged-dot-none_*` under `<family>.moe/grouped` (the exact
# path's under `<family>.moe/exact/grouped`).
def _moe(prefix):
    """The kernels of an expert layer under `prefix`: the grouped
    products, and since PR 52 the row moves (`ops/moe_rows.py`), in the
    first buffer and on the exact path behind it."""
    return {f"{prefix}{path}/{part}" for path in ("", "/exact")
            for part in ("grouped", "gather", "scatter")}


KERNEL_SCOPES = {
    "gpt2": {"transformer.attn"},
    "afmoe": {"afmoe.attn.sliding_attention", "afmoe.attn.full_attention",
              "afmoe.attn.sliding_attention/qkv/heads",
              "afmoe.attn.full_attention/qkv/heads", *_moe("afmoe.moe")},
    "granitehybrid": {"granite.mamba.scan", "granite.mamba.conv",
                      "granite.attn"},
    "mellum": {"mellum.attn.sliding_attention", "mellum.attn.full_attention",
               "mellum.attn.sliding_attention/qkv/heads",
               "mellum.attn.full_attention/qkv/heads", *_moe("mellum.moe")},
    "keye": {"keye.attn.full_attention/select",
             "keye.attn.full_attention/sparse", *_moe("keye.moe")},
    "nemotronh": {"nemotronh.mamba.scan", "nemotronh.mamba.conv",
                  "nemotronh.mamba.gate_norm", "nemotronh.attn",
                  *_moe("nemotronh.moe")},
    "joyai": {"joyai.attn", *_moe("joyai.moe"), "joyai.mtp/joyai.attn",
              *_moe("joyai.mtp/joyai.moe")},
    "lfm2": {"lfm2.conv.gate_conv", "lfm2.attn", *_moe("lfm2.moe")},
    "kimilinear": {"kimi.kda.conv", "kimi.kda.scan", "kimi.kda.gate_norm",
                   "kimi.attn", *_moe("kimi.moe")},
    "sdarmoe": {"sdar.attn.block_diffusion",
                "sdar.attn.block_diffusion/qkv/heads", *_moe("sdar.moe")},
    "ouro": {"ouro.attn.full_attention",
             "ouro.attn.full_attention/qkv/heads"},
}
PRODUCTS = ("fusion", "custom-call", "dot", "convolution", "ragged-dot")
WORK = ("dot_general", "conv_general_dilated", "pallas_call")


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e topology here: {e!r:.200}")
    return list(topo.devices)


@pytest.fixture
def no_persistent_cache():
    """As `test_tpu_aot_compile.py`: what is compiled for a described
    device cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture
def kernels(monkeypatch):
    """The Mosaic kernels, not the interpreter, in a step traced here."""
    monkeypatch.setattr(fa, "_use_interpret", lambda interpret: False)
    monkeypatch.setattr(ssd, "_use_interpret", lambda interpret: False)


@functools.lru_cache(maxsize=None)
def _family(name: str):
    """`(a cell-like with the tiny job, the family)`: the cell's own
    configuration cut to tiny widths, and for the two expert families to
    fewer layers (one of each kind), which the compiler's time asks.
    Made once a family for all the module's tests, which read it."""
    cell = tiny.tiny_cell(FAMILIES[name][0])
    if name == "afmoe":     # dense sliding, expert sliding, expert full
        cell = dataclasses.replace(cell,
                                   config=tiny_afmoe.config(layers=[1, 6, 7]))
    elif name == "mellum":  # sliding, full
        cell = dataclasses.replace(cell,
                                   config=tiny_mellum.config(layers=[2, 3]))
    elif name == "keye":    # two layers; heads and indexer heads as wide
        config = tiny_keye.config(layers=[0, 1])    # as the chip's tiles
        config["published"].update(
            head_dim=128, rope_scaling={"mrope_section": [16, 24, 24],
                                        "rope_type": "default"},
            sa_config={**config["published"]["sa_config"],
                       "indexer_head_dim": 64})
        cell = dataclasses.replace(cell, config=config)
    elif name == "nemotronh":   # M, *, E; experts of HALF a lane tile
        config = tiny_nemotronh.config(layers=[4, 5, 6])
        config["published"].update(tiny_nemotronh.ON_THE_CHIP)
        cell = dataclasses.replace(cell, config=config)
    elif name == "joyai":       # dense, expert, the module; the published
        config = tiny_joyai.config(layers=[0, 1])       # head: 192 and 128
        config["published"].update(tiny_joyai.ON_THE_CHIP)
        config["program_options"]["pinned"]["attn_impl"] = "flash"
        config["job"]["seq_len"] = 128
        cell = dataclasses.replace(cell, config=config,
                                   job={**cell.job, **config["job"]})
    elif name == "lfm2":    # conv dense, attention expert, conv expert
        config = tiny_lfm2.config(layers=[1, 2, 3])
        config["published"].update(tiny_lfm2.ON_THE_CHIP)
        config["assumed"]["head_dim"] = 64
        cell = dataclasses.replace(cell, config=config)
    elif name == "kimilinear":  # KDA dense, KDA expert, latent expert
        config = tiny_kimilinear.config(layers=[1, 7, 8])
        config["published"].update(tiny_kimilinear.ON_THE_CHIP)
        cell = dataclasses.replace(cell, config=config)
    elif name == "sdarmoe":     # two of the six layers, all alike
        cell = dataclasses.replace(cell,
                                   config=tiny_sdarmoe.config(layers=[0, 1]))
    elif name == "ouro":
        # ONE of the eight layers, walked twice: with two the loop in a
        # loop's own instructions (a slice of a layer's weights and a
        # gradient's write into the stack a leaf, in BOTH loops, and at
        # these widths a buffer in fast memory each) outnumber two
        # layers' products, 84 of 209, under `_check`'s floors
        cell = dataclasses.replace(
            cell, config=tiny_ouro.config(layers=[0], walks=2))
        # heads of a lane tile over the tiny cell's 128 rows, which
        # `ops/head_norm_rope.py`'s rule sends to its kernels (PR 65: the
        # turn alone, no head normed)
        cell.config["published"].update(head_dim=128)
    if name in ("afmoe", "mellum", "keye", "sdarmoe"):
        # the narrowest widths the grouped kernels tile: a lane tile each
        # (the tiny cuts' 64 and 32 go to `lax.ragged_dot`, the compiler's)
        config = copy.deepcopy(cell.config)
        config["published"].update(hidden_size=128, moe_intermediate_size=128)
        if name != "keye":
            # and heads of a lane tile, which `ops/head_norm_rope.py`'s
            # rule sends to its kernels (keye's are, above; it turns its
            # queries and keys by `keye.rotary`, the compiler's)
            config["published"].update(head_dim=128)
        cell = dataclasses.replace(cell, config=config)
    family = measure._module("families", cell.config["family"]).Family(
        cell.config, cell.job)
    if name == "granitehybrid":
        # the tiny cell's chunk of 64 is the interpreter's: the chip's
        # compiler wants 128 lanes
        family.cfg = dataclasses.replace(family.cfg, mamba_chunk_size=128)
    return cell, family


def _abstract_step(family, per_chip_batch: int, devices, loss=None):
    """`(step, its abstract arguments)` through the normal entry points
    on a mesh of `devices`."""
    mesh = bps.make_mesh(devices=devices)
    opt = bps.DistributedOptimizer(family.optimizer())
    step = bps.build_train_step(loss or family.loss, opt, mesh, donate=True)
    params = jax.eval_shape(family.init, jax.random.key(0))
    state = jax.eval_shape(opt.init, params)
    batch = jax.eval_shape(
        lambda k: family.make_batch(k, per_chip_batch * len(devices)),
        jax.random.key(1))

    def on(spec):
        sharding = NamedSharding(mesh, spec)
        return lambda t: jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=sharding), t)
    return step, (on(P())(params), on(P())(state), on(P("dp"))(batch))


def _map(family, per_chip_batch, devices, loss=None):
    step, args = _abstract_step(family, per_chip_batch, devices, loss)
    text = jax.jit(step).lower(*args).compile().as_text()
    return text, devprof.parse_step_scopes(text)


_CYCLES = re.compile(r'"estimated_cycles":"(\d+)"')


def _check(scopes: dict, text: str, expected: set, remat: bool):
    products = {n: e for n, e in scopes.items()
                if devprof._OPCODE.search(_line(text, n)).group(1) in PRODUCTS}
    assert len(products) > 20
    assert all(e["pass"] in devprof.PASSES for e in scopes.values())
    placed = {n for n, e in products.items()
              if e["scope"] or e["pass"] == "optimizer"}
    without = sorted((n, products[n]["op_name"])
                     for n in set(products) - placed)
    # Where the work is, nothing is without a scope: whatever holds a
    # matrix product or a convolution (a fusion's path is its product's),
    # and the program's own kernel calls.
    work = {n for n, e in products.items()
            if e["op_name"].split(";")[0].rsplit("/", 1)[-1] in WORK}
    assert len(work) > 10 and work <= placed, sorted(work - placed)
    # What stays without is the layer loop's own (a scan's slice of a
    # layer's weights, its write of a gradient into the stack: a path and
    # a pass, and no scope, as no model part opened one round them) and
    # what the compiler made with no path at all (a cast or a layout copy
    # of a whole stack of weights hoisted out of the loop, a pad): a fixed
    # handful an instruction, which at tiny depth and tiny widths two
    # layers' products do not outweigh as the cells' dozens do.  So the
    # floors here are low, by count and by the compiler's own estimate of
    # each one's cycles; the chip's time a cell is PERF.md's.
    assert len(placed) >= 0.75 * len(products), without
    cycles = {n: int((_CYCLES.search(_line(text, n)) or [0, 0])[1])
              for n in products}
    assert sum(cycles[n] for n in placed) >= 0.80 * sum(
        cycles.values()), without
    by_pass = {p: [n for n, e in products.items() if e["pass"] == p]
               for p in devprof.PASSES}
    for p in ("forward", "backward", "optimizer"):
        assert by_pass[p], p
    assert bool(by_pass["recompute"]) == remat
    seen = {e["scope"] for e in scopes.values()}
    assert expected <= seen, expected - seen


def _line(text: str, name: str) -> str:
    at = text.index(f"%{name} = ")
    return text[at + len(name) + 4:text.index("\n", at)]


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_every_familys_tiny_step_is_mapped(name, v5e, kernels,
                                           no_persistent_cache):
    """Every product, fusion and kernel call of the compiled step has a
    pass, every one that holds a product or is a kernel a scope of the
    program's or the optimizer, and most of the rest (`_check`);
    the passes a checkpointed model has are all there; the scopes the
    model opens all show; and the kernels lie under the names the trace
    has called them by since before the map."""
    cell, family = _family(name)
    cell_name, expected, remat = FAMILIES[name]
    text, scopes = _map(family, int(cell.job["per_chip_batch"]), v5e[:1])
    _check(scopes, text, expected, remat)
    # the program's own Pallas calls, the grouped products among them:
    # each under the scope its call was traced in, and nothing is LENT
    # one (only a kernel the compiler made itself, with no path, is:
    # `lax.ragged_dot`'s was, until PR 40)
    kernels = {n: e for n, e in scopes.items()
               if 'custom_call_target="tpu_custom_call"' in _line(text, n)}
    assert {e["scope"] for e in kernels.values()} == KERNEL_SCOPES.get(
        name, set())
    assert not [n for n, e in scopes.items() if e.get("lent")]
    grouped = {n: e for n, e in kernels.items()
               if n.startswith("ragged-dot-none_")}
    moves = {n: e for n, e in kernels.items() if n.startswith("moe_rows_")}
    prefix = SCOPE_PREFIX.get(name, name)
    if name in ("afmoe", "mellum", "keye", "nemotronh", "joyai", "lfm2",
                "kimilinear", "sdarmoe"):
        # the rows move by the program's kernel in every pass, under the
        # scopes `moe.move_ms` and `moe.move_kernel_share` read
        assert all(e["scope"].rsplit("/", 1)[1] in ("gather", "scatter")
                   and e["op_name"].endswith("/pallas_call")
                   for e in moves.values())
        assert {"forward", "recompute", "backward"} <= {
            e["pass"] for e in moves.values()}
        assert {n.split(".")[0].rsplit("_", 1)[1] for n in grouped} == {
            "fwd", "drows", "dweights"}
        assert all(e["scope"].startswith((f"{prefix}.moe/",
                                          f"{prefix}.mtp/{prefix}.moe/"))
                   and e["pass"] != "other" for e in grouped.values())
        assert {"forward", "recompute", "backward"} <= {
            e["pass"] for e in grouped.values()}
        assert "ragged-dot-metadata" not in text
    else:
        assert not grouped and not moves
    if name in ("granitehybrid", "nemotronh"):
        # the mixers' convolution is the program's kernel in every pass
        # (PR 56), under the scope `mamba.conv_ms` and
        # `mamba.conv_kernel_share` read
        convs = {n: e for n, e in kernels.items()
                 if n.startswith("mamba_conv_")}
        assert {(n.split(".")[0], e["pass"]) for n, e in convs.items()} == {
            ("mamba_conv_fwd", "forward"), ("mamba_conv_fwd", "recompute"),
            ("mamba_conv_bwd", "backward")}
        assert all(e["scope"].endswith(".mamba.conv")
                   and e["op_name"].endswith("/pallas_call")
                   for e in convs.values())
    if name in ("nemotronh", "kimilinear"):
        # the gated norm over stretches of the width is the program's
        # kernel in every pass (PR 60), under the scope
        # `gate_norm.ms_per_step` and `gate_norm.kernel_share` read;
        # granite's, over the whole width, is the compiler's
        norms = {n: e for n, e in kernels.items()
                 if n.startswith("gated_norm_")}
        assert {(n.split(".")[0], e["pass"]) for n, e in norms.items()} == {
            ("gated_norm_fwd", "forward"), ("gated_norm_fwd", "recompute"),
            ("gated_norm_bwd", "backward")}
        assert all(e["scope"].endswith(".gate_norm")
                   and e["op_name"].endswith("/pallas_call")
                   for e in norms.values())
    if name in ("afmoe", "mellum", "sdarmoe", "ouro"):
        # the queries' and keys' norm and turn (ouro, PR 65: the turn
        # alone) are the program's kernel in every pass (PR 62), under the
        # scope `qk_heads.ms_per_step` and `qk_heads.kernel_share` read, a
        # child of `qkv` (`attn.around_kernel_ms` counts it)
        heads = {n: e for n, e in kernels.items()
                 if n.startswith("head_norm_rope_")}
        assert {(n.split(".")[0], e["pass"]) for n, e in heads.items()} == {
            ("head_norm_rope_fwd", "forward"),
            ("head_norm_rope_fwd", "recompute"),
            ("head_norm_rope_bwd", "backward")}
        assert all(e["scope"].endswith("/qkv/heads")
                   and e["op_name"].endswith("/pallas_call")
                   for e in heads.values())
        # and the scope holds nothing else's pass (the rotary tables, which
        # the compiler lifts out of the layer loop, are made outside it):
        # `qk_heads.kernel_share` counts (scope, pass) pairs
        assert {e["pass"] for e in scopes.values()
                if e["scope"].endswith("/qkv/heads")} == {
            "forward", "recompute", "backward"}
    if name == "kimilinear":
        # the scan and the convolution round it are the program's kernels
        # in every pass, under the scopes `kimi.kda_mixer_ms` reads
        mine = {(n.split(".")[0], e["pass"], e["scope"])
                for n, e in kernels.items()
                if n.startswith(("kda_", "mamba_conv_"))}
        assert mine == {
            (call + kind, which, "kimi.kda." + scope)
            for call, scope in (("kda_", "scan"), ("mamba_conv_", "conv"))
            for kind, which in (("fwd" + "_c64" * (call == "kda_"),
                                 "forward"),
                                ("fwd" + "_c64" * (call == "kda_"),
                                 "recompute"),
                                ("bwd" + "_c64" * (call == "kda_"),
                                 "backward"))}


def test_the_dp4_step_is_mapped_with_the_exchange_in_the_optimizer(
        v5e, no_persistent_cache):
    """The shard_map path on four described chips: the same scopes, and
    the gradients' sums under `byteps.optimizer/byteps.bucket<N>`."""
    cell, family = _family("vgg")
    text, scopes = _map(family, int(cell.job["per_chip_batch"]), v5e)
    _check(scopes, text, FAMILIES["vgg"][1], remat=False)
    sums = [e for n, e in scopes.items()
            if devprof._OPCODE.search(_line(text, n)).group(1).startswith(
                "all-reduce")]
    assert sums
    assert all(e["pass"] == "optimizer" and e["scope"].startswith(
        "byteps.optimizer/byteps.bucket") for e in sums), sums


def test_without_a_checkpoint_nothing_is_recomputed(v5e, kernels,
                                                    no_persistent_cache):
    from byteps_tpu.models import transformer as tfm
    cell, family = _family("gpt2")
    cfg = dataclasses.replace(family.cfg, remat=False)
    _, scopes = _map(family, 2, v5e[:1],
                     loss=lambda p, b: tfm.loss_fn(p, b, cfg))
    assert {"forward", "backward"} <= {e["pass"] for e in scopes.values()}
    # the streamed head checkpoints its chunks whatever the layers do
    assert {e["scope"] for e in scopes.values()
            if e["pass"] == "recompute"} == {"transformer.head"}


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_the_scopes_change_no_operation(name, monkeypatch):
    """The step lowers to the SAME text with `jax.named_scope` a null
    context: a scope is metadata and nothing else."""
    cell, family = _family(name)

    def lowered():
        """The step built and lowered anew: its text, and its text with
        the name stacks (a lowering is the cost, so both from one)."""
        step, args = _abstract_step(family, int(cell.job["per_chip_batch"]),
                                    jax.devices()[:1])
        one = jax.jit(step).lower(*args)
        return one.as_text(), one.as_text(debug_info=True)
    scoped, scoped_with_names = lowered()
    assert len(scoped) > 50_000         # a whole train step, not a stub
    assert "byteps.optimizer" in scoped_with_names
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain, plain_with_names = lowered()
    assert "byteps.optimizer" not in plain_with_names
    assert plain == scoped


@pytest.mark.parametrize("op_name, scope, which", [
    # a scope is a component with a "." in it; the pass is the transform's
    ("jit(step)/jvp(transformer.mlp)/dot_general", "transformer.mlp",
     "forward"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/afmoe.moe/.route/top_k", "afmoe.moe/route",
     "recompute"),
    # a family this module has never heard of, a child of its own naming
    ("jit(step)/transpose(jvp(llama4.ffn))/.gate/mul", "llama4.ffn/gate",
     "backward"),
    # a child that a transform wraps (a backward pass written by hand)
    ("jit(step)/transpose(jvp())/mellum.moe/.exact/jvp(.gather)/gather",
     "mellum.moe/exact/gather", "backward"),
    ("jit(step)/byteps.optimizer/byteps.bucket3/psum",
     "byteps.optimizer/byteps.bucket3", "optimizer"),
    # no scope: a function's name, JAX's own words, a module system's,
    # the primitive itself, a parameter's name, the compiler's own
    ("jit(models.step)/jvp(jit(utils.take))/while/body/Dense_0/dot_general",
     "", "forward"),
    ("jit(step)/jvp()/while/body/fam.head", "", "forward"),
    ("params['layers'][0]['in_proj.w']", "", "other"),
    ("ragged-dot-none", "", "other"),
    ("", "", "other"),
    # two instructions made one: the first path is read
    ("jit(step)/jvp(granite.head)/reshape;jvp(granite.head)/reshape",
     "granite.head", "forward"),
])
def test_a_scope_is_told_by_its_form_not_by_a_list_of_names(op_name, scope,
                                                            which):
    assert devprof.classify_op_name(op_name) == (scope, which)


_KERNEL_STEP = """
HloModule jit_step, entry_computation_layout={()->f32[8]{0}}

ENTRY %main.1 () -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f1, metadata={op_name="jit(step)/jvp(fam.moe)/.gather/gather"}
  %meta.1 = (s32[4]{0}, s32[4]{0}) custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-metadata"}
  %gte.1 = s32[4]{0} get-tuple-element(%meta.1), index=0
  %copy.1 = f32[8]{0} copy(%fusion.1)
  %ragged-dot-none.1 = f32[8]{0} custom-call(%gte.1, %copy.1), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %bitcast.1 = f32[8]{0} bitcast(%ragged-dot-none.1)
  %fusion.2 = f32[8]{0} fusion(%bitcast.1), kind=kLoop, calls=%f2, metadata={op_name="jit(step)/jvp(fam.moe)/.grouped/mul"}
  %convert.1 = f32[8]{0} convert(%p)
  %fusion.3 = f32[8]{0} fusion(%convert.1), kind=kLoop, calls=%f3, metadata={op_name="jit(step)/jvp(fam.moe)/.scatter/add"}
  %orphan.1 = f32[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="some-kernel"}
  ROOT %add.1 = f32[8]{0} add(%fusion.2, %fusion.3), metadata={op_name="jit(step)/jvp(other.part)/add"}
}
"""


def test_only_the_compilers_own_kernels_are_lent_a_scope():
    """A kernel with no path takes what the scopes round it share, through
    the instructions that only hand a value on, and says that it was
    lent; an instruction with no path that is no kernel stays without,
    next to whatever it stands; so does a kernel among none with a scope."""
    scopes = devprof.parse_step_scopes(_KERNEL_STEP)
    assert scopes["ragged-dot-none.1"] == {
        "scope": "fam.moe", "pass": "forward", "lent": True,
        "op_name": "ragged-dot-none"}       # gather and grouped share it
    assert scopes["meta.1"]["scope"] == "fam.moe/gather"    # its one maker
    assert scopes["meta.1"]["lent"]
    assert scopes["convert.1"] == {"scope": "", "pass": "other",
                                   "op_name": ""}
    assert scopes["orphan.1"]["scope"] == "" and "lent" not in scopes[
        "orphan.1"]
    assert [n for n, e in scopes.items() if e.get("lent")] == [
        "meta.1", "ragged-dot-none.1"]


def _tiny_step(scope: str):
    """A train step small enough to compile in a second, one scope of the
    program's vocabulary in it."""
    def loss(w, x):
        with jax.named_scope(scope):
            return jnp.sum(jnp.tanh(x @ w) ** 2)
    mesh = bps.make_mesh(devices=jax.devices()[:1])
    opt = bps.DistributedOptimizer(optax.sgd(0.1))
    w = jnp.ones((16, 16))
    return (bps.build_train_step(loss, opt, mesh, donate=False),
            (w, opt.init(w), jnp.ones((4, 16))))


def _top_scopes(scopes: dict) -> set:
    return {e["scope"].split("/")[0] for e in scopes.values() if e["scope"]}


@pytest.fixture
def fresh_record(monkeypatch):
    monkeypatch.setattr(devprof, "_step_record", None)
    monkeypatch.setattr(devprof, "_step_scopes", None)


def test_the_map_is_of_the_step_that_was_built(fresh_record):
    assert bps.get_step_scopes() is None        # no step yet
    step, args = _tiny_step("transformer.mlp")
    step(*args)
    scopes = bps.get_step_scopes()
    assert _top_scopes(scopes) == {"transformer.mlp", "byteps.optimizer"}
    assert {"forward", "backward", "optimizer"} <= {
        e["pass"] for e in scopes.values()}
    assert bps.get_step_scopes() is scopes      # kept, not compiled again


def test_the_record_is_taken_when_a_call_compiled_and_on_no_other(
        fresh_record, monkeypatch):
    calls = []
    real = devprof.remember_step
    monkeypatch.setattr(devprof, "remember_step",
                        lambda fn, args: (calls.append(fn), real(fn, args)))
    step, args = _tiny_step("transformer.mlp")
    for _ in range(3):
        step(*args)
    assert len(calls) == 1
    # a batch of another shape compiles the same callable again: the
    # record is of the program that now runs
    w, state, _ = args
    for _ in range(2):
        step(w, state, jnp.ones((8, 16)))
    assert len(calls) == 2 and calls[0] is calls[1]
    assert devprof._step_record[1][2].shape == (8, 16)
    # on a mesh: at each compile of the callable (the second call's state
    # comes back placed), none on a later step
    del calls[:]
    mesh = bps.make_mesh(devices=jax.devices()[:2])
    opt = bps.DistributedOptimizer(optax.sgd(0.1))
    dp = bps.build_train_step(
        lambda w, x: jnp.sum((x @ w) ** 2), opt, mesh, donate=False)
    w, state = jnp.ones((16, 16)), opt.init(jnp.ones((16, 16)))
    for _ in range(4):
        w, state, _ = dp(w, state, jnp.ones((4, 16)))
    assert 1 <= len(calls) <= 2 and len(set(map(id, calls))) == 1
    record = devprof._step_record
    assert all(isinstance(leaf, jax.ShapeDtypeStruct)
               for leaf in jax.tree.leaves(record[1]))


def test_a_step_that_cannot_be_compiled_gives_none_and_one_line(
        fresh_record, caplog):
    class Broken:
        def lower(self, *args):
            raise RuntimeError("no such program")
    devprof.remember_step(Broken(), (jnp.ones(3),))
    logger = devprof.get_logger()        # it hands nothing up to the root
    level = logger.level
    logger.setLevel(logging.WARNING)     # conftest pins ERROR
    logger.addHandler(caplog.handler)
    try:
        assert bps.get_step_scopes() is None
        assert bps.get_step_scopes() is None
    finally:
        logger.removeHandler(caplog.handler)
        logger.setLevel(level)
    assert [r.getMessage() for r in caplog.records].count(
        "no scope map of the step: RuntimeError('no such program')") == 1


@pytest.fixture
def cache_in(tmp_path):
    """The persistent cache in a directory of this test's, taking every
    program however small."""
    from jax.experimental.compilation_cache import compilation_cache
    names = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
             "jax_compilation_cache_include_metadata_in_key",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compilation_cache.reset_cache()
    yield tmp_path
    for n, v in before.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def test_a_renamed_scope_shows_through_the_persistent_cache(
        fresh_record, cache_in):
    """Two trees whose operations are the same and whose scopes differ,
    one cache, JAX's default key (metadata left out): the second tree's
    step is still its own, because `build_train_step` compiles the step,
    and nothing else, with its name stacks in the key
    (`compile_cache.scopes_in_key`); and the map is of the executable the
    step holds, for which nothing is compiled or fetched."""
    assert not jax.config.jax_compilation_cache_include_metadata_in_key
    from jax._src import monitoring
    asked = []

    def listen(event, **kw):
        asked.append(event)
    jax.monitoring.register_event_listener(listen)
    try:
        for scope in ("transformer.mlp", "granite.mlp"):
            step, args = _tiny_step(scope)
            step(*args)
            assert not (
                jax.config.jax_compilation_cache_include_metadata_in_key)
            del asked[:]
            assert _top_scopes(bps.get_step_scopes()) == {
                scope, "byteps.optimizer"}
            assert not [e for e in asked if "compilation_cache" in e]
    finally:
        monitoring.unregister_event_listener(listen)
    assert os.listdir(cache_in)


def _step_from_source(scope: str, blank_lines: int):
    """`_tiny_step` with its loss compiled from text, so that the same
    loss can stand on another line of its file."""
    names = {"jax": jax, "jnp": jnp}
    exec(compile("\n" * blank_lines + f"""
def loss(w, x):
    with jax.named_scope({scope!r}):
        return jnp.sum(jnp.tanh(x @ w) ** 2)
""", __file__, "exec"), names)
    mesh = bps.make_mesh(devices=jax.devices()[:1])
    opt = bps.DistributedOptimizer(optax.sgd(0.1))
    w = jnp.ones((16, 16))
    return (bps.build_train_step(names["loss"], opt, mesh, donate=False),
            (w, opt.init(w), jnp.ones((4, 16))))


def test_a_moved_line_finds_the_steps_entry_and_a_renamed_scope_does_not(
        fresh_record, cache_in):
    """What the step's key holds of its metadata is the name stacks and
    not the source lines: a later tree that only shifts a line of a file
    the step is traced through compiles nothing anew."""
    log = bps.utils.compile_cache.install()     # the cache's answers

    def run(scope, blank_lines):
        before = dict(log.by_cache)
        step, args = _step_from_source(scope, blank_lines)
        step(*args)
        return (log.by_cache["hit"] - before["hit"],
                log.by_cache["miss"] - before["miss"])
    first = run("transformer.mlp", 0)
    assert first[1] >= 1                        # the step itself, cold
    assert run("transformer.mlp", 7) == (first[0] + first[1], 0)
    assert run("granite.mlp", 7)[1] == 1        # the step alone
    assert _top_scopes(bps.get_step_scopes()) == {"granite.mlp",
                                                  "byteps.optimizer"}


def test_without_its_scopes_in_the_key_the_older_trees_scopes_come_back(
        fresh_record, cache_in, monkeypatch):
    """The hazard itself, so that nobody takes `scopes_in_key` out of the
    step: with JAX's default key alone the second tree is handed the first
    tree's executable, scopes and all."""
    from byteps_tpu.utils import compile_cache
    monkeypatch.setattr(compile_cache, "scopes_in_key",
                        contextlib.nullcontext)
    seen = []
    for scope in ("transformer.mlp", "granite.mlp"):
        step, args = _tiny_step(scope)
        step(*args)
        seen.append(_top_scopes(bps.get_step_scopes()) - {"byteps.optimizer"})
    assert seen == [{"transformer.mlp"}, {"transformer.mlp"}]
