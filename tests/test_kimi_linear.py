"""The kimi_linear model (`byteps_tpu/models/kimi_linear.py`: layers that
mix the sequence with the delta rule of `ops/kda.py` or with latent
attention without positions, a dense feed-forward or sigmoid-routed
experts round a shared one) at tiny widths in float32 against its plain
reference (`benchmark/reference/kimilinear.py`): the loss and every
gradient leaf of one chip's share; the test that ties the 32 shares of an
expert layer, the shared expert counted once, to the uncut layer; the
parameter count of the cell from the built tree; the stack plan.  (The
whole model against the reference is `tests/test_kimi_linear_whole.py`,
another worker's; the twenty-one broken variants run with the benchmark's
own tests, `benchmark/tests/test_kimilinear.py`.)"""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import manifest
from benchmark.reference import kimilinear as reference
from benchmark.tests import tiny_kimilinear
from byteps_tpu.models import kimi_linear
from byteps_tpu.parallel import dropless_moe

KDA, MLA = kimi_linear.KDA, kimi_linear.MLA
DENSE, MOE = kimi_linear.DENSE, kimi_linear.MOE


def against_reference(family, seed=0):
    """The program's loss and gradients against the reference's, each at
    its own choice of experts (in float32 they choose alike): `(loss
    difference, worst leaf's relative difference, its name)`."""
    from benchmark.harness import seeded
    params = seeded.params(family, seed)
    batch = seeded.batch(family, seed, 2)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(family.loss))(params, batch)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p, b: reference.loss(p, b, family.spec)))(params, batch)
    off = jax.tree_util.tree_flatten_with_path(jax.tree.map(
        lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)),
        grads, want_grads))[0]
    name, worst = max(off, key=lambda kv: kv[1])
    return (abs(float(loss) - float(want)) / float(want), worst,
            jax.tree_util.keystr(name))


def test_a_share_against_the_reference():
    """In float32 the program IS the reference up to rounding: the model's
    layer 1 (KDA, dense) and layer 8 (latent attention, experts), every
    kind of mixer and of feed-forward in two runs, as one chip's eight
    experts over a slice of the vocabulary that does not start at 0."""
    config = tiny_kimilinear.config(layers=[1, 8])
    config["held"]["vocab_start"] = 20480
    from benchmark.families import kimilinear
    family = kimilinear.Family(config, config["job"])
    family.cfg = dataclasses.replace(family.cfg, dtype=jnp.float32)
    assert kimi_linear.stack_plan(family.cfg) == ((KDA, DENSE, 1),
                                                  (MLA, MOE, 1))
    assert len(family.cfg.held) == 8 and family.cfg.num_experts == 256
    loss_off, worst, name = against_reference(family)
    assert loss_off < 1e-6 and worst < 2e-4, (loss_off, worst, name)
    import byteps_tpu as bps
    metrics = bps.get_metrics()
    for kept in kimi_linear.KEPT_NAMES:
        assert metrics[f'bps_remat_kept_bytes{{name="{kept}"}}'] > 0
    assert metrics["bps_kda_scan_layers"] == 1
    assert metrics["bps_kda_kernel"] == 1 and metrics["bps_kda_chunk"] == 64
    assert metrics["bps_kda_bwd_solve_products"] == 12
    assert metrics["bps_layer_plan_stacks"] == 2


def test_the_shares_add_up_to_the_model():
    """Guide, section 4: over the 32 chips that share a layer, the routed
    parts the shares compute plus the shared expert counted ONCE are the
    uncut reference's expert layer, for the same tokens."""
    family = tiny_kimilinear.family(jnp.float32, layers=[8])
    cfg, spec = family.cfg, family.spec
    E, D, F = cfg.num_experts, cfg.hidden_size, cfg.moe_intermediate_size
    chips, held = 32, E // 32
    assert held == len(cfg.held) == 8
    k = jax.random.split(jax.random.key(0), 8)
    whole = {
        "router_w": jax.random.normal(k[0], (D, E)) / 8,
        "expert_gate_w": jax.random.normal(k[1], (E, D, F)) / 8,
        "expert_up_w": jax.random.normal(k[2], (E, D, F)) / 8,
        "expert_down_w": jax.random.normal(k[3], (E, F, D)) / 6,
    }
    shared_w = [jax.random.normal(k[4], (D, F)) / 8,
                jax.random.normal(k[5], (D, F)) / 8,
                jax.random.normal(k[6], (F, D)) / 6]
    m = jax.random.normal(k[7], (96, D))
    with jax.default_matmul_precision("highest"):
        uncut, _ = reference.routed_experts(
            m, whole, {**spec, "held": tuple(range(E))})
        uncut = uncut + reference.swiglu(m, *shared_w)

    @jax.jit
    def first_eight(router_w, experts):
        return dropless_moe.held_experts(
            m, router_w, experts,
            dataclasses.replace(cfg.moe, held=tuple(range(held))))

    with jax.default_matmul_precision("highest"):
        total, rows = reference.swiglu(m, *shared_w), 0
    for chip in range(chips):
        # chip c's eight experts moved to the front of the router's
        # columns: one program for the 32 shares
        mine = jnp.arange(held) + chip * held
        out, routing = first_eight(
            jnp.roll(whole["router_w"], -chip * held, axis=1),
            {n: whole["expert_" + n][mine]
             for n in ("gate_w", "up_w", "down_w")})
        total, rows = total + out, rows + int(routing.held_rows)
    assert rows == m.shape[0] * cfg.num_experts_per_tok
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=3e-5, rtol=3e-5)


def test_the_cells_tree_counts_the_parameters_the_configuration_states():
    """`benchmark/configs/kimi-linear-48b-a3b-instruct.json`
    `deployment.parameters`, from the tree the cell's family builds
    (shapes alone): ISSUE 57's table, part by part."""
    from benchmark.families import kimilinear
    with open(os.path.join(manifest.BENCH, "configs",
                           tiny_kimilinear.NAME + ".json")) as f:
        config = json.load(f)
    family = kimilinear.Family(config, config["job"])
    tree = jax.eval_shape(family.init, jax.random.key(0))

    def count(t):
        return sum(math.prod(a.shape) for a in jax.tree.leaves(t))
    assert kimi_linear.stack_plan(family.cfg) == (
        (KDA, DENSE, 1), (KDA, MOE, 3), (MLA, MOE, 1))
    assert [count(g) for g in tree["layers"]] == [
        103_219_872, 3 * 103_809_696, 93_410_304]
    mixer = {k: v for k, v in tree["layers"][0].items() if k in (
        "qkv_w", "conv_w", "A_log", "f_a_w", "f_b_w", "dt_bias", "beta_w",
        "g_a_w", "g_b_w", "o_norm", "out_w")}
    assert count(mixer) == 39_514_272
    latent = {k: v for k, v in tree["layers"][2].items() if k in (
        "q_w", "down_w", "kv_a_ln", "kv_up_w", "attn_out_w")}
    assert count(latent) == 29_114_880
    assert count(tree["embed"]) == count(tree["head"]) == 47_185_920
    assert count(tree) == 602_433_408
    assert not any("expert_bias" in g for g in tree["layers"])
    assert f"{count(tree):,}" in config["deployment"]["parameters"]
    # what a step's tokens give a held expert
    tokens = config["job"]["per_chip_batch"] * config["job"]["seq_len"]
    assert tokens * family.cfg.num_experts_per_tok / 256 == 1024
    assert family.cfg.moe.norm_eps == 1e-20
    # every published width as it is, the three cuts the listed ones
    published = config["published"]
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    for key, value in published.items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert (published["hidden_size"], published["intermediate_size"],
            published["moe_intermediate_size"],
            published["num_experts_per_token"],
            published["kv_lora_rank"]) == (2304, 9216, 1024, 8, 512)
    assert family.layer_types == (KDA, KDA, KDA, KDA, MLA)


def test_runs_and_not_layers_are_what_is_traced():
    """The published order (three KDA layers to every latent-attention
    one, the first layer dense) is 15 runs of its 27 layers; the cell's
    five layers are three, each one `lax.scan` over its stacked leaves."""
    with open(os.path.join(manifest.BENCH, "configs",
                           tiny_kimilinear.NAME + ".json")) as f:
        lin = json.load(f)["published"]["linear_attn_config"]
    types = tuple(KDA if i in lin["kda_layers"] else MLA
                  for i in range(1, 28))
    assert set(lin["kda_layers"]) | set(lin["full_attn_layers"]) == set(
        range(1, 28))
    cfg = tiny_kimilinear.family().cfg
    model = dataclasses.replace(cfg, layer_types=types, num_dense_layers=1)
    plan = kimi_linear.stack_plan(model)
    assert len(plan) == 15 and sum(n for _, _, n in plan) == 27
    assert plan[:3] == ((KDA, DENSE, 1), (KDA, MOE, 2), (MLA, MOE, 1))
    assert plan[-2:] == ((KDA, MOE, 2), (MLA, MOE, 1))
    params = jax.eval_shape(lambda k: kimi_linear.init_params(k, cfg),
                            jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, t: kimi_linear.forward_hidden(p, t, cfg))(params, tokens)
    lengths = [e.params["length"] for e in jaxpr.jaxpr.eqns
               if e.primitive.name == "scan"]
    assert lengths == [1, 3, 1]
