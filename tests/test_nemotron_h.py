"""The `nemotron_h` decoder (`byteps_tpu/models/nemotron_h.py`) at tiny
widths on the CPU against the plain reference of
`benchmark/reference/nemotronh.py` (loss and every leaf's gradient, in
float32 to rounding and in the cell's bfloat16 to the family's tiny
limits), the plan by kind on the cell's nine layers and on the published
52, the shares of an expert layer against the uncut reference, the gated
norm by groups, the parameter count of the built tree against the
configuration's, and the scopes and gauges a traced step leaves.  The
broken variants are `tests/test_nemotron_h_variants.py`'s, so that the two
files run on two workers."""

import dataclasses
import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import byteps_tpu as bps
from benchmark.families import nemotronh as family_nemotronh
from benchmark.harness import manifest
from benchmark.reference import nemotronh as reference
from benchmark.tests import tiny_nemotronh
from byteps_tpu.models import granite_hybrid, nemotron_h
from byteps_tpu.ops import flash_attention, ssd
from byteps_tpu.parallel import dropless_moe
from family_cases import Cases
from testutil import (eqns, is_flash_forward, is_product,
                      mixer_trains_as_with_the_jnp_convolution, named_bytes)

CASES = Cases(tiny_nemotronh)
_family = CASES.family
PUBLISHED = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
def test_the_convolutions_kernel_trains_the_model_the_jnp_form_did(
        monkeypatch, dtype, tol):
    """One `M` layer with its convolution on `ops/short_conv.py`'s kernels
    (through `granite_hybrid._mamba`, no edit of this model's) against the
    same program with the jnp form in its place, as it was until PR 56."""
    mixer_trains_as_with_the_jnp_convolution(
        _family(dtype, layers=[4]), monkeypatch, tol)


@pytest.mark.parametrize("pattern,counts", [
    ("MEMEM*EME", {"mamba": 4, "moe": 4, "attention": 1}),
    (PUBLISHED, {"mamba": 23, "moe": 23, "attention": 6}),
], ids=["the_cells_stage", "the_published_52"])
def test_the_plan_by_kind_walks_the_published_order(pattern, counts):
    """Three stacks whatever the order: layer i of kind c takes the next
    set of c's leaves, and the tree holds one stack a kind, as long as
    the kind has layers."""
    family = _family(jnp.float32)
    cfg = dataclasses.replace(family.cfg,
                              layer_kinds=nemotron_h.kinds_of(pattern))
    plan = nemotron_h.layer_plan(cfg)
    assert [k for k, _ in plan] == [nemotron_h.LETTERS[c] for c in pattern]
    for kind, n in counts.items():
        assert [j for k, j in plan if k == kind] == list(range(n))
        assert cfg.count(kind) == n
    shapes = jax.eval_shape(
        lambda k: nemotron_h.init_params(k, cfg), jax.random.key(0))
    assert set(shapes) == {"embed", "head", "final_ln", *counts}
    for kind, n in counts.items():
        assert {a.shape[0] for a in jax.tree.leaves(shapes[kind])} == {n}
    # granite's plan by period would make a run, a group of leaves and a
    # compiled body of every change of kind
    granite = dataclasses.replace(
        granite_hybrid.GraniteHybridConfig(
            vocab_size=8, hidden_size=8, layer_types=("mamba",),
            intermediate_size=8, num_heads=1, num_kv_heads=1, head_dim=8,
            mamba_n_heads=1, mamba_d_head=8, mamba_d_state=8),
        layer_types=tuple("attention" if c == "*" else "mamba"
                          for c in pattern if c != "E"))
    periods, runs = granite_hybrid._stack_plan(granite)
    assert periods == 1 and len(runs) > 2
    with pytest.raises(ValueError, match="no layer of kind '-'"):
        nemotron_h.kinds_of("ME-*")


def test_a_traced_step_leaves_the_plans_gauges_and_the_models_scopes():
    family = _family(jnp.bfloat16)
    params = jax.eval_shape(family.init, jax.random.key(0))
    batch = jax.eval_shape(lambda k: family.make_batch(k, 1),
                           jax.random.key(0))
    text = jax.jit(jax.grad(family.loss)).lower(params, batch).as_text(
        debug_info=True)
    for scope in ("nemotronh.embed", "nemotronh.mamba.in_proj",
                  "nemotronh.mamba.conv", "nemotronh.mamba.scan",
                  "nemotronh.mamba.gate_norm", "nemotronh.mamba.out_proj",
                  "nemotronh.attn", "nemotronh.moe", "nemotronh.head"):
        assert scope in text, scope
    assert "granite." not in text
    jaxpr = str(jax.make_jaxpr(jax.grad(family.loss))(params, batch))
    assert "ssd_fwd_c64" in jaxpr and "ssd_bwd_c64" in jaxpr
    metrics = bps.get_metrics()
    assert metrics["bps_layer_plan_stacks"] == 3
    assert metrics['bps_layer_plan_layers{kind="mamba"}'] == 4
    assert metrics['bps_layer_plan_layers{kind="moe"}'] == 4
    assert metrics['bps_layer_plan_layers{kind="attention"}'] == 1
    assert metrics["bps_ssd_scan_groups"] == 2
    assert metrics["bps_ssd_scan_layers"] == 4
    assert metrics["bps_ssd_chunk"] == 64


@pytest.mark.parametrize("again", [1, 2],
                         ids=["kept_by_name", "a_plain_checkpoint"])
def test_a_recompute_makes_again_only_what_its_layer_does_not_keep(
        monkeypatch, again):
    """In the differentiated step, a call for each time it runs: a *
    layer's flash forward kernel, an M layer's `in_proj` product, an E
    layer's score product, top-k and sorts are there ONCE a layer, where
    the same walk under a plain `jax.checkpoint` (a policy of no name)
    shows each twice.  What is not kept is made again either way: the
    scan's forward kernel."""
    family = _family(jnp.float32)
    cfg = family.cfg
    if again == 2:
        monkeypatch.setattr(nemotron_h, "KEPT_NAMES", ())
    params = jax.eval_shape(family.init, jax.random.key(0))
    batch = jax.eval_shape(lambda k: family.make_batch(k, 1),
                           jax.random.key(0))
    B, S, D = 1, family.seq_len, cfg.hidden_size
    wide = cfg.d_inner + cfg.conv_dim + cfg.mamba_n_heads
    step = list(eqns(jax.make_jaxpr(jax.grad(family.loss))(params,
                                                           batch).jaxpr))

    def count(found):
        return sum(map(found, step))
    a_layer = {
        nemotron_h.ATTENTION: [is_flash_forward],
        nemotron_h.MAMBA: [lambda e: is_product(e, (B, S, D), (D, wide))],
        nemotron_h.MOE: [
            lambda e: is_product(e, (B * S, D), (D, cfg.num_experts)),
            lambda e: e.primitive.name == "top_k",
            lambda e: e.primitive.name == "sort"],
    }
    # (an E layer's plan sorts twice: the pairs by expert, and each
    # pair's place in that list)
    sorts = {nemotron_h.MOE: [1, 1, 2]}
    for kind, made in a_layer.items():
        assert [count(m) for m in made] == [
            again * cfg.count(kind) * n
            for n in sorts.get(kind, [1] * len(made))], kind
    assert count(lambda e: e.primitive.name == "pallas_call"
                 and e.params["name"] == "ssd_fwd_c64") == 2 * cfg.count(
                     nemotron_h.MAMBA)


def test_remat_kept_says_what_the_names_hold():
    """`bps_remat_kept_*{name}`: the layers that keep a name and the bytes
    they hold together, which are the bytes of what `checkpoint_name`
    names in the traced step; at the cell's shapes `o` and `lse` of the *
    layer (134 + 2 MB), four M layers' `in_proj` results ([16384, 10304]
    bfloat16, 338 MB each) and four E layers' routing (10.0 MB each):
    1.53 GB."""
    family = _family(jnp.bfloat16)
    params = jax.eval_shape(family.init, jax.random.key(0))
    batch = jax.eval_shape(lambda k: family.make_batch(k, 1),
                           jax.random.key(0))
    named = named_bytes(jax.make_jaxpr(family.loss)(params, batch).jaxpr)
    metrics = bps.get_metrics()
    layers = {flash_attention.KEPT_NAME: 1, granite_hybrid.IN_PROJ_NAME: 4,
              dropless_moe.ROUTING_NAME: 4}
    assert set(nemotron_h.KEPT_NAMES) == set(layers) == set(named)
    for name, n in layers.items():
        assert metrics[f'bps_remat_kept_layers{{name="{name}"}}'] == n
        assert metrics[f'bps_remat_kept_bytes{{name="{name}"}}'] == named[
            name], name

    with open(os.path.join(manifest.BENCH, "configs",
                           tiny_nemotronh.NAME + ".json")) as f:
        config = json.load(f)
    family = family_nemotronh.Family(config, config["job"])
    jax.eval_shape(family.loss,
                   jax.eval_shape(family.init, jax.random.key(0)),
                   jax.eval_shape(lambda k: family.make_batch(k, 1),
                                  jax.random.key(0)))
    metrics = bps.get_metrics()
    assert [metrics[f'bps_remat_kept_bytes{{name="{name}"}}']
            for name in layers] == [
        32 * 16384 * (128 * 2 + 4), 4 * 16384 * 10304 * 2,
        4 * 4 * (16384 * (128 + 3 * 6) + 98816 + 2 * 8)]
    assert 32 * 16384 * 260 + 4 * 337_641_472 + 4 * 9_963_584 \
        == 1_526_735_104


@pytest.mark.parametrize("impl,copies", [("kernel", 0), ("jnp", 4)])
def test_the_scan_says_which_layout_ran(monkeypatch, impl, copies):
    """The scan's two gauges of its layout in this family's step: a kernel
    program's slab (at this cut's 2 groups of 4 heads of 8 the whole
    width, every head a program; at the published 8 groups of 8 heads of
    64 one group's 512 lanes), and only the `jnp` form makes transposed
    copies of x, y and their gradients."""
    family = _family(jnp.bfloat16, layers=[4, 5, 6])
    if impl != "kernel":
        monkeypatch.setattr(ssd, "ssd_scan",
                            functools.partial(ssd.ssd_scan, impl=impl))
    params = jax.eval_shape(family.init, jax.random.key(0))
    batch = jax.eval_shape(lambda k: family.make_batch(k, 1),
                           jax.random.key(0))
    jax.eval_shape(jax.grad(family.loss), params, batch)
    metrics = bps.get_metrics()
    assert metrics["bps_ssd_lane_block"] == 2 * 4 * 8
    assert metrics["bps_ssd_wide_copies"] == copies
    assert metrics["bps_ssd_scan_groups"] == 2


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_grouped_norm_is_the_norm_of_each_stretch(dtype):
    """`_gate_norm_grouped` sums and spreads over stretches of the width
    as the mixer has it (no reshape that would re-tile the array on the
    chip): values and the three gradients against the norm of the array
    reshaped to [.., groups, width / groups], which is what it was."""
    cfg = _family(dtype, layers=[4]).cfg
    groups, width = 4, 32
    ks = jax.random.split(jax.random.key(5), 4)
    y, z = (jax.random.normal(k, (2, 6, groups * width), dtype)
            for k in ks[:2])
    scale = 1.0 + 0.1 * jax.random.normal(ks[2], (groups * width,))
    w = jax.random.normal(ks[3], y.shape)

    def reshaped(y, z, scale):
        gated = y * jax.nn.silu(z)
        split = (*gated.shape[:-1], groups, width)
        return granite_hybrid._norm(gated.reshape(split),
                                    scale.reshape(groups, width),
                                    cfg).reshape(gated.shape)

    def value_and_grads(fn):
        return jax.value_and_grad(
            lambda *a: (fn(*a).astype(jnp.float32) * w).sum(),
            argnums=(0, 1, 2))(y, z, scale)
    want, want_grads = value_and_grads(reshaped)
    got, got_grads = value_and_grads(
        lambda y, z, scale: granite_hybrid._gate_norm_grouped(
            y, z, scale, cfg, groups))
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(float(got), float(want), rtol=tol, atol=tol)
    for a, e in zip(got_grads, want_grads):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(e, np.float32), rtol=tol,
                                   atol=tol)
    sums = granite_hybrid._stretch_sums(w, groups)
    np.testing.assert_allclose(
        np.asarray(sums), np.asarray(w.reshape(2, 6, groups, width).sum(-1)),
        rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(granite_hybrid._spread(sums, width)),
        np.asarray(jnp.repeat(sums, width, axis=-1)))


def test_the_shares_add_up_to_the_layer():
    """Guide, section 4: over the sixteen chips that share a layer, the
    routed parts the shares compute, with the shared expert (which every
    chip computes alike) counted ONCE, are the uncut reference's expert
    layer for the same tokens, every pair on exactly one chip; and the
    eight slices' logits laid side by side are the whole head's."""
    family = _family(jnp.float32, layers=[1])
    cfg, spec = family.cfg, family.spec
    E, D = cfg.num_experts, cfg.hidden_size
    F, Fs = cfg.moe_intermediate_size, cfg.moe_shared_intermediate_size
    k = jax.random.split(jax.random.key(0), 6)
    whole = {
        "router_w": jax.random.normal(k[0], (D, E)) / 8,
        "expert_up_w": jax.random.normal(k[1], (E, D, F)) / 8,
        "expert_down_w": jax.random.normal(k[2], (E, F, D)) / 5,
        "shared_up_w": jax.random.normal(k[3], (D, Fs)) / 8,
        "shared_down_w": jax.random.normal(k[4], (Fs, D)) / 7,
    }
    m = jax.random.normal(k[5], (1, 192, D))
    with jax.default_matmul_precision("highest"):
        uncut, _ = reference.moe_part(
            m, whole, {**spec, "held": tuple(range(E)), "mlp_block": 64})
    total, rows = 0.0, 0
    for chip in range(16):
        held = tuple(range(chip * E // 16, (chip + 1) * E // 16))
        assert len(held) == 8
        moe = dataclasses.replace(cfg.moe, held=held)
        experts = {n: whole["expert_" + n][jnp.asarray(held)]
                   for n in ("up_w", "down_w")}
        part, routing = dropless_moe.held_experts(m[0], whole["router_w"],
                                                  experts, moe)
        total, rows = total + part, rows + int(routing.held_rows)
    shared = nemotron_h._relu2(m, whole["shared_up_w"],
                               whole["shared_down_w"], jnp.float32)
    assert rows == m.shape[1] * cfg.num_experts_per_tok
    np.testing.assert_allclose(np.asarray(total + shared[0]),
                               np.asarray(uncut[0]), atol=3e-5, rtol=3e-5)

    V = 8 * 24
    head = jax.random.normal(k[0], (V, D))
    x = jax.random.normal(k[1], (2, 16, D))
    side_by_side = jnp.concatenate(
        [nemotron_h.head_logits(x, head[c * 24:(c + 1) * 24])
         for c in range(8)], axis=-1)
    np.testing.assert_allclose(np.asarray(side_by_side),
                               np.asarray(x @ head.T), atol=1e-4, rtol=1e-5)


def test_an_expert_is_two_matrices_with_a_squared_relu_between():
    """`dropless_moe.held_experts` without a `gate_w`: every expert held,
    top-2 of 4, against a loop over the experts; the scale 2.5 on weights
    that sum to 1, and the bias moves the choice and not the weights."""
    D, F, E, T = 16, 24, 4, 64
    k = jax.random.split(jax.random.key(3), 4)
    x = jax.random.normal(k[0], (T, D))
    router = jax.random.normal(k[1], (D, E))
    up = jax.random.normal(k[2], (E, D, F)) / 4
    down = jax.random.normal(k[3], (E, F, D)) / 5
    cfg = dropless_moe.MoEConfig(num_experts=E, top_k=2, held=(0, 1, 2, 3),
                                 route_scale=2.5, row_multiple=8)
    out, routing = dropless_moe.held_experts(
        x, router, {"up_w": up, "down_w": down}, cfg)
    np.testing.assert_allclose(np.asarray(routing.weights.sum(-1)), 2.5,
                               rtol=1e-6)
    with jax.default_matmul_precision("highest"):
        want = sum(
            jnp.where(routing.sel == e, routing.weights, 0.0).sum(-1)[:, None]
            * (jnp.square(jax.nn.relu(x @ up[e])) @ down[e])
            for e in range(E))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
    bias = jnp.asarray([0.0, 0.0, 0.0, 10.0])
    sel, weights = dropless_moe.route(x, router, cfg, expert_bias=bias)
    assert bool((sel == 3).any(-1).all())
    scores = jax.nn.sigmoid(x @ router)
    chosen = jnp.take_along_axis(scores, sel, -1)
    np.testing.assert_allclose(
        np.asarray(weights),
        np.asarray(2.5 * chosen / chosen.sum(-1, keepdims=True)), rtol=1e-5)


def test_the_gated_norm_is_by_groups_and_after_the_gate():
    cfg = _family(jnp.float32).cfg
    k = jax.random.split(jax.random.key(1), 3)
    y = jax.random.normal(k[0], (2, 8, 64)) * jnp.arange(1, 65)
    z = jax.random.normal(k[1], (2, 8, 64))
    scale = jax.random.normal(k[2], (64,))
    got = granite_hybrid._gate_norm_grouped(y, z, scale, cfg, 2)
    want = reference.gated_group_norm(y, z, scale, 2, cfg.rms_norm_eps)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-6)
    one = granite_hybrid._gate_norm(y, z, scale, cfg)
    np.testing.assert_allclose(
        np.asarray(one),
        np.asarray(reference.gated_group_norm(y, z, scale, 1,
                                              cfg.rms_norm_eps)),
        rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(one - got).max()) > 0.1


def test_model_flops_and_parameters_at_the_published_widths():
    """The configuration file's own count, from the built tree:
    666,962,944 parameters; the published keys are all there with the
    catalog's values but for the three that are `reduced`; and the FLOPs
    a token the issue reckoned."""
    with open(os.path.join(manifest.BENCH, "configs",
                           tiny_nemotronh.NAME + ".json")) as f:
        config = json.load(f)
    family = family_nemotronh.Family(config, config["job"])
    shapes = jax.eval_shape(family.init, jax.random.key(0))
    count = sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))
    assert count == 666_962_944
    assert f"{count:,}" in config["deployment"]["parameters"]
    per_kind = {kind: sum(math.prod(a.shape[1:])
                          for a in jax.tree.leaves(shapes[kind]))
                for kind in ("mamba", "moe", "attention")}
    assert per_kind == {"mamba": 38_744_896, "moe": 100_125_312,
                        "attention": 23_399_040}
    for key, value in config["published"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert {k: config[k] for k in config["reduced"]} == {
        "num_hidden_layers": 9, "n_routed_experts": 8, "vocab_size": 16384}
    assert family.kinds == nemotron_h.kinds_of("MEMEM*EME")
    assert (family.cfg.d_inner, family.cfg.conv_dim) == (4096, 6144)
    assert shapes["mamba"]["in_proj_w"].shape == (4, 2688, 10304)
    assert shapes["moe"]["expert_up_w"].shape == (4, 8, 2688, 1856)
    assert family.cfg.moe.buffer_rows(16384) == 7680
    assert config["job"] == {"per_chip_batch": 1, "seq_len": 16384,
                             "optimizer": {"name": "adamw",
                                           "learning_rate": 0.0001}}
    assert config["deployment"]["chips_a_layer"] == 16
    assert {"assumed", "left_out", "held", "deployment"} <= set(config)
    flops = family.model_flops_per_sample() / family.seq_len
    # 6 x 318.4M matmul parameters a token (1.91 GFLOP), attention's
    # triangle 0.40, four scans 0.04
    assert 2.3e9 < flops < 2.4e9


def test_the_new_code_stays_out_of_the_other_cells_imports():
    """The other families import nothing of the nemotron_h model."""
    import subprocess
    import sys
    from testutil import cpu_env
    code = ("import sys, byteps_tpu, byteps_tpu.models.afmoe, "
            "benchmark.families.afmoe, benchmark.families.gpt2, "
            "benchmark.families.vgg, benchmark.families.granitehybrid, "
            "benchmark.families.mellum, benchmark.families.keye, "
            "benchmark.jobs.ingraph; "
            "bad = [m for m in sys.modules if 'nemotron' in m]"
            "; assert not bad, bad")
    r = subprocess.run([sys.executable, "-c", code], env=cpu_env(),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
