"""The lfm2 model (`byteps_tpu/models/lfm2.py`: layers that mix the
sequence with a doubly gated 3-tap convolution or with grouped attention,
a dense feed-forward or sigmoid-routed experts) at tiny widths in float32
against its plain reference (`benchmark/reference/lfm2.py`), through the
benchmark's own family and comparison: the loss and every gradient leaf,
the whole model and a share; the test that ties the eight shares of an
expert layer to the uncut layer; the convolution's kernels in the
interpreter against the jnp form; the parameter count of the cell from
the built tree; the stack plan; and `dropless_moe.route` as it was for the
other families.  (The thirteen broken variants run with the benchmark's own
tests, `benchmark/tests/test_lfm2.py`.)"""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import correct, manifest
from benchmark.reference import lfm2 as reference
from benchmark.tests import tiny_lfm2
from byteps_tpu.models import lfm2
from byteps_tpu.ops import short_conv, ssd
from byteps_tpu.parallel import dropless_moe


@pytest.mark.parametrize("whole", [False, True], ids=["share", "whole_model"])
def test_against_reference(whole):
    """In float32 the program IS the reference up to rounding: a dense
    conv layer, an attention expert layer and a conv expert layer (every
    kind of mixer and of feed-forward, three runs), as one chip's eight
    experts over a slice of the vocabulary that does not start at 0, and
    with all 64 experts and the whole vocabulary."""
    cut = dict(layers=[1, 2, 3])
    if whole:
        cut.update(experts=range(64), vocab=1024)
    config = tiny_lfm2.config(**cut)
    config["reference_check"].update(tiny_lfm2.FLOAT32)
    if whole:
        config["published"]["vocab_size"] = 1024
    else:
        config["held"]["vocab_start"] = 8192
    from benchmark.families import lfm2 as family_lfm2
    family = family_lfm2.Family(config, config["job"])
    family.cfg = dataclasses.replace(family.cfg, dtype=jnp.float32)
    assert lfm2.stack_plan(family.cfg) == (
        (lfm2.CONV, lfm2.DENSE, 1), (lfm2.ATTENTION, lfm2.MOE, 1),
        (lfm2.CONV, lfm2.MOE, 1))
    assert len(family.cfg.held) == (64 if whole else 8)
    got = tiny_lfm2.agreement(family)
    assert correct.agreement_ok(got, family.reference_check), got
    seen = family.selection[-1]
    assert got["worst_leaf"] and seen["swapped_share"] == 0
    assert max(seen[k] for k in ("router_rel_diff", "experts_rel_diff",
                                 "attn_rel_diff", "conv_rel_diff")) < 1e-5
    import byteps_tpu as bps
    metrics = bps.get_metrics()
    for name in lfm2.KEPT_NAMES:
        assert metrics[f'bps_remat_kept_bytes{{name="{name}"}}'] > 0


def test_the_shares_add_up_to_the_model():
    """Guide, section 4: over the eight chips that share a layer, the
    parts the shares compute are the uncut reference's expert layer, for
    the same tokens.  Nothing is shared, so nothing is counted once."""
    family = tiny_lfm2.family(jnp.float32, layers=[3])
    cfg, spec = family.cfg, family.spec
    E, D, F = cfg.num_experts, cfg.hidden_size, cfg.moe_intermediate_size
    k = jax.random.split(jax.random.key(0), 5)
    whole = {
        "router_w": jax.random.normal(k[0], (D, E)) / 8,
        "expert_gate_w": jax.random.normal(k[1], (E, D, F)) / 8,
        "expert_up_w": jax.random.normal(k[2], (E, D, F)) / 8,
        "expert_down_w": jax.random.normal(k[3], (E, F, D)) / 6,
    }
    m = jax.random.normal(k[4], (96, D))
    with jax.default_matmul_precision("highest"):
        uncut, _ = reference.routed_experts(
            m, whole, {**spec, "held": tuple(range(E))})

    @jax.jit
    def first_eight(router_w, experts):
        return dropless_moe.held_experts(
            m, router_w, experts,
            dataclasses.replace(cfg.moe, held=tuple(range(E // 8))))

    total, rows = 0.0, 0
    for chip in range(8):
        # chip c's eight experts moved to the front of the router's
        # columns: one program for the eight shares
        held = jnp.arange(E // 8) + chip * (E // 8)
        out, routing = first_eight(
            jnp.roll(whole["router_w"], -chip * (E // 8), axis=1),
            {n: whole["expert_" + n][held]
             for n in ("gate_w", "up_w", "down_w")})
        total, rows = total + out, rows + int(routing.held_rows)
    assert rows == m.shape[0] * cfg.num_experts_per_tok
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=2e-5, rtol=2e-5)


def _oracle(bcx, taps):
    """The jnp form: `ssd.causal_conv1d` between two products."""
    b, c, x = jnp.split(bcx, 3, axis=-1)
    return c * ssd.causal_conv1d(b * x, taps)


@pytest.mark.parametrize("shape", [(2, 40, 32, 16), (2, 72, 256, 32),
                                   (1, 64, 1024, 0)],
                         ids=["ragged_narrow", "ragged_two_chunks",
                              "one_block_two_chunks"])
def test_the_kernels_against_the_jnp_form(shape):
    """Forward, d bcx and dw in the interpreter: two sequences of a length
    that is no multiple of the block (the last block is padded, the
    second sequence starts inside a block), and a width walked in chunks."""
    batch, seq_len, width, block = shape
    k = jax.random.split(jax.random.key(1), 3)
    bcx = jax.random.normal(k[0], (batch, seq_len, 3 * width))
    taps = jax.random.normal(k[1], (3, width))
    g = jax.random.normal(k[2], (batch, seq_len, width))
    out, vjp = jax.vjp(
        lambda a, b: short_conv.gated_short_conv(a, b, block), bcx, taps)
    want, want_vjp = jax.vjp(_oracle, bcx, taps)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)
    for a, b in zip(vjp(g), want_vjp(g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=1e-5)


def test_the_gated_calls_lower_to_what_they_lowered_to():
    """`gated_short_conv`'s two calls at the cell's shape
    ([4, 8192, 3 x 2048] bfloat16), forward and backward: the first 16 hex
    digits of sha256 over the lowered text AS PR 55'S TREE lowered it
    (commit 0bd15b7), before `ops/short_conv.py` held a second operator.
    The Mamba mixers' kernels share the module's helpers and must leave
    this operator's bodies, and so the lfm2 cell's compiled step, alone; a
    change that means to change them writes its own digits here."""
    import hashlib
    bcx = jax.ShapeDtypeStruct((4, 8192, 6144), jnp.bfloat16)
    taps = jax.ShapeDtypeStruct((3, 2048), jnp.float32)
    g = jax.ShapeDtypeStruct((4, 8192, 2048), jnp.bfloat16)

    def both(bcx, taps, g):
        y, vjp = jax.vjp(lambda a, b: short_conv.gated_short_conv(
            a, b, interpret=True), bcx, taps)
        return y, vjp(g)
    text = jax.jit(both).lower(bcx, taps, g).as_text()
    assert "loc(" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == (
        "133b197c01105c1e")


def test_the_kernel_is_causal_and_a_sequence_its_own():
    """One position perturbed: nothing before it moves, the two after it
    do, and nothing in the next sequence (no tap reaches across)."""
    k = jax.random.split(jax.random.key(2), 2)
    bcx = jax.random.normal(k[0], (2, 48, 3 * 128), jnp.float32)
    taps = jax.random.normal(k[1], (3, 128))
    t = 46                              # two before the first sequence's end
    moved = bcx.at[0, t].add(1.0)
    a, b = (short_conv.gated_short_conv(x, taps, 32) for x in (bcx, moved))
    changed = np.asarray(jnp.abs(a - b).max(-1) > 0)
    assert not changed[0, :t].any() and changed[0, t:t + 2].all()
    assert not changed[1].any()
    # and the gradient of a position reads nothing before it
    g = jnp.zeros_like(a).at[1, 0].set(1.0)
    dx = jax.vjp(lambda x: short_conv.gated_short_conv(x, taps, 32), bcx)[1](
        g)[0]
    touched = np.asarray(jnp.abs(dx).max(-1) > 0)
    assert touched[1, 0] and touched.sum() == 1


def test_the_cells_tree_counts_the_parameters_the_configuration_states():
    """`benchmark/configs/lfm2-24b-a2b.json` `deployment.parameters`, from
    the tree the cell's family builds (shapes alone)."""
    from benchmark.families import lfm2 as family_lfm2
    with open(os.path.join(manifest.BENCH, "configs",
                           tiny_lfm2.NAME + ".json")) as f:
        config = json.load(f)
    family = family_lfm2.Family(config, config["job"])
    tree = jax.eval_shape(family.init, jax.random.key(0))

    def count(t):
        return sum(math.prod(a.shape) for a in jax.tree.leaves(t))
    plan = lfm2.stack_plan(family.cfg)
    assert plan == ((lfm2.CONV, lfm2.DENSE, 1), (lfm2.ATTENTION, lfm2.MOE, 1),
                    (lfm2.CONV, lfm2.MOE, 3), (lfm2.ATTENTION, lfm2.MOE, 1),
                    (lfm2.CONV, lfm2.MOE, 1))
    assert [count(g) for g in tree["layers"]] == [
        89_139_200, 86_118_528, 3 * 92_416_000, 86_118_528, 92_416_000]
    conv = {k: v for k, v in tree["layers"][0].items()
            if k in ("in_proj_w", "conv_w", "out_proj_w")}
    assert count(conv) == 16_783_360
    assert count(tree["embed"]) == 16_777_216 and "head" not in tree
    assert count(tree) == 647_819_520
    assert not any("expert_bias" in g for g in tree["layers"])
    assert f"{count(tree):,}" in config["deployment"]["parameters"]
    # what a step's tokens give a held expert: its deployment load
    tokens = config["job"]["per_chip_batch"] * config["job"]["seq_len"]
    assert tokens * family.cfg.num_experts_per_tok / 64 == 2048
    assert family.cfg.moe.buffer_rows(tokens) == 20_480
    assert family.cfg.moe.norm_eps == 1e-6
    # every published width as it is, the four cuts the listed ones
    published = config["published"]
    assert config["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "num_experts", "vocab_size"]
    for key, value in published.items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert (published["hidden_size"], published["conv_L_cache"],
            published["intermediate_size"], published["moe_intermediate_size"],
            published["num_experts_per_tok"]) == (2048, 3, 11776, 1536, 4)


def _scans(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _scans(sub, found)
    return found


def test_runs_and_not_layers_are_what_is_traced():
    """The published order is 21 runs of its 40 layers; the cell's seven
    layers are five, each one `lax.scan` over its stacked leaves: the
    forward pass traces five layer bodies, its longest run three layers
    deep."""
    with open(os.path.join(manifest.BENCH, "configs",
                           tiny_lfm2.NAME + ".json")) as f:
        types = tuple(json.load(f)["published"]["layer_types"])
    model = dataclasses.replace(tiny_lfm2.family().cfg, layer_types=types,
                                num_dense_layers=2)
    plan = lfm2.stack_plan(model)
    assert len(plan) == 21 and sum(n for _, _, n in plan) == 40
    assert plan[:3] == ((lfm2.CONV, lfm2.DENSE, 2),
                        (lfm2.ATTENTION, lfm2.MOE, 1),
                        (lfm2.CONV, lfm2.MOE, 3))
    cfg = tiny_lfm2.family().cfg
    params = jax.eval_shape(lambda k: lfm2.init_params(k, cfg),
                            jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, t: lfm2.forward_hidden(p, t, cfg))(
        params, tokens)
    lengths = [e.params["length"] for e in jaxpr.jaxpr.eqns
               if e.primitive.name == "scan"]
    assert lengths == [1, 1, 3, 1, 1]


def test_route_is_what_it_was_for_the_other_families():
    """`MoEConfig.norm_eps` defaults to the 1e-20 that was written into
    `route`: for trinity-mini's settings (sigmoid, 8 of 128, normed, scale
    2.826) the weights are bit for bit the formula's, and lfm2's 1e-6 is
    another number."""
    cfg = dropless_moe.MoEConfig(num_experts=128, top_k=8,
                                 held=tuple(range(16)), route_scale=2.826)
    assert cfg.norm_eps == 1e-20
    k = jax.random.split(jax.random.key(3), 2)
    x = jax.random.normal(k[0], (64, 32))
    w = jax.random.normal(k[1], (32, 128)) / 4
    # op by op on both sides: a compiler is free to fuse a division its
    # own way, and what is held here is the arithmetic that was written
    sel, weights = dropless_moe.route(x, w, cfg)

    def formula(x, w, eps):
        with jax.default_matmul_precision("highest"):
            scores = jax.nn.sigmoid(x @ w)
        _, own = jax.lax.top_k(scores, 8)
        chosen = jnp.take_along_axis(scores, own, -1)
        return own, chosen / (chosen.sum(-1, keepdims=True) + eps) * 2.826
    own, want = formula(x, w, 1e-20)
    np.testing.assert_array_equal(np.asarray(sel), np.asarray(own))
    np.testing.assert_array_equal(np.asarray(weights), np.asarray(want))
    other = dropless_moe.route(
        x, w, dataclasses.replace(cfg, norm_eps=1e-2))[1]
    assert float(jnp.abs(other - weights).max()) > 1e-4
