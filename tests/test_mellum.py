"""The mellum model (`byteps_tpu/models/mellum.py`) at tiny widths against
its plain float32 reference (`benchmark/reference/mellum.py`), through the
benchmark's own family and comparison: loss and every gradient leaf,
sliding and full layers, a share and the whole model, the choice of
experts apart from the arithmetic (the ten broken variants:
`test_mellum_variants.py`), the test that ties one chip's share to the
whole layer, rotary positions against their closed form, and the machinery
shared with `afmoe.py` left as it was."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.families import mellum as family_mellum
from benchmark.reference import mellum as reference
from benchmark.tests import tiny_mellum
from byteps_tpu.models import afmoe, mellum
from byteps_tpu.models import transformer as tfm
from byteps_tpu.parallel import dropless_moe
from family_cases import Cases

CASES = Cases(tiny_mellum, family_mellum.Family)
_family = CASES.family


def test_the_shares_add_up_to_the_layer():
    """Guide, section 4: over the four chips that share a layer, the
    routed parts the shares compute (there is no shared expert to count
    once) are the uncut reference's expert layer, for the same tokens,
    every pair on exactly one chip; and the four slices' logits laid side
    by side are the whole head's."""
    family = _family(jnp.float32, layers=[0])
    cfg, spec = family.cfg, family.spec
    E, D, F = cfg.num_experts, cfg.hidden_size, cfg.moe_intermediate_size
    k = jax.random.split(jax.random.key(0), 5)
    whole = {
        "router_w": jax.random.normal(k[0], (D, E)) / 8,
        "expert_gate_w": jax.random.normal(k[1], (E, D, F)) / 8,
        "expert_up_w": jax.random.normal(k[2], (E, D, F)) / 8,
        "expert_down_w": jax.random.normal(k[3], (E, F, D)) / 6,
    }
    m = jax.random.normal(k[4], (192, D))
    with jax.default_matmul_precision("highest"):
        uncut, _ = reference.experts_layer(
            m, whole, {**spec, "held": tuple(range(E))})
    total, rows = 0.0, 0
    for chip in range(4):
        held = tuple(range(chip * E // 4, (chip + 1) * E // 4))
        assert len(held) == 16
        moe = dataclasses.replace(cfg.moe, held=held)
        experts = {n: whole["expert_" + n][jnp.asarray(held)]
                   for n in ("gate_w", "up_w", "down_w")}
        part, routing = dropless_moe.held_experts(m, whole["router_w"],
                                                  experts, moe)
        total, rows = total + part, rows + int(routing.held_rows)
    assert rows == m.shape[0] * cfg.num_experts_per_tok
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=2e-5, rtol=2e-5)

    V = 4 * 40
    head = jax.random.normal(k[0], (V, D))
    x = jax.random.normal(k[1], (2, 16, D))
    side_by_side = jnp.concatenate(
        [afmoe.head_logits(x, head[c * 40:(c + 1) * 40]) for c in range(4)],
        axis=-1)
    np.testing.assert_allclose(np.asarray(side_by_side),
                               np.asarray(x @ head.T), atol=1e-4, rtol=1e-5)


def test_a_shares_backward_pass_holds_the_held_weight():
    """`MoEConfig.hold_held_weight`, which the model sets: the value of a
    share's expert layer and of its weights is what it was (bit for bit,
    so the shares still add up); the logits of the experts held elsewhere
    get NO gradient, those of the held ones a gradient that sums to
    nothing over them (their competition among themselves), where without
    it the held logits are pushed one way against the absent ones; and a
    model that holds every expert lowers to the text it had."""
    family = _family(jnp.float32, layers=[0])
    cfg = family.cfg
    assert cfg.moe.hold_held_weight
    k = jax.random.split(jax.random.key(1), 3)
    lp = jax.tree.map(lambda a: a[0], mellum.init_params(k[0], cfg)["moe"])
    m = jax.random.normal(k[1], (96, cfg.hidden_size))
    g = jax.random.normal(k[2], m.shape)
    experts = {n: lp["expert_" + n] for n in ("gate_w", "up_w", "down_w")}

    def value_and_router_grad(moe):
        def f(router_w):
            routed, routing = dropless_moe.held_experts(m, router_w, experts,
                                                        moe)
            return (routed * g).sum(), (routed, routing.weights)
        (_, aux), gw = jax.value_and_grad(f, has_aux=True)(lp["router_w"])
        return aux, gw

    plain, gw_plain = value_and_router_grad(
        dataclasses.replace(cfg.moe, hold_held_weight=False))
    held_const, gw = value_and_router_grad(cfg.moe)
    jax.tree.map(np.testing.assert_array_equal, plain, held_const)
    held = np.asarray(cfg.held)
    absent = np.setdiff1d(np.arange(cfg.num_experts), held)
    scale = float(jnp.abs(gw_plain).max())
    assert float(jnp.abs(gw_plain[:, absent]).max()) > 1e-2 * scale
    assert float(jnp.abs(gw[:, absent]).max()) < 1e-5 * scale
    assert float(jnp.abs(gw[:, held].sum(-1)).max()) < 1e-5 * scale
    assert float(jnp.abs(gw_plain[:, held].sum(-1)).max()) > 1e-2 * scale

    whole = dataclasses.replace(cfg.moe, held=tuple(range(cfg.num_experts)))
    every = {n: jnp.concatenate([w] * 4) for n, w in experts.items()}
    texts = [jax.jit(lambda m: dropless_moe.held_experts(
        m, lp["router_w"], every, moe)[0]).lower(m).as_text()
        for moe in (whole, dataclasses.replace(whole,
                                               hold_held_weight=False))]
    assert texts[0] == texts[1]


def test_softmax_router_with_no_bias_and_no_scale():
    """`dropless_moe.route`'s softmax branch: probabilities over ALL the
    experts, the top k of them, weights that sum to 1."""
    cfg = dropless_moe.MoEConfig(num_experts=64, top_k=8,
                                 held=tuple(range(16)), score_func="softmax")
    x = jax.random.normal(jax.random.key(0), (50, 32))
    w = jax.random.normal(jax.random.key(1), (32, 64))
    sel, weights = dropless_moe.route(x, w, cfg)
    p = jax.nn.softmax(np.asarray(x, np.float64) @ np.asarray(w, np.float64))
    want = np.sort(np.argsort(-np.asarray(p), axis=-1)[:, :8], axis=-1)
    assert np.array_equal(np.sort(np.asarray(sel), axis=-1), want)
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.0, rtol=1e-6)
    chosen = np.take_along_axis(np.asarray(p), np.asarray(sel), axis=-1)
    np.testing.assert_allclose(
        np.asarray(weights), chosen / chosen.sum(-1, keepdims=True),
        rtol=1e-5)
    raw = dropless_moe.route(x, w, dataclasses.replace(cfg, route_norm=False))
    np.testing.assert_allclose(np.asarray(raw[1]), chosen, rtol=1e-5)


def test_yarn_against_the_closed_form_at_the_published_numbers():
    yarn = mellum.Yarn(factor=16, original_positions=8192, beta_fast=32,
                       beta_slow=1, attention_factor=1.2772588722239782)
    got = mellum.yarn_inv_freq(128, 500000.0, yarn)
    assert got.shape == (64,) and got.dtype == np.float32
    theta = 500000.0

    def d(r):
        return 128 * math.log(8192 / (2 * math.pi * r)) / (
            2 * math.log(theta))
    low, high = math.floor(d(32)), math.ceil(d(1))
    assert (low, high) == (18, 35)
    for i in range(64):
        extrap = theta ** (-2 * i / 128)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want = extrap / 16 * ramp + extrap * (1 - ramp)
        assert got[i] == pytest.approx(want, rel=1e-6), i
    # fast pairs keep their frequency, slow ones turn 16 times slower
    assert got[18] == pytest.approx(theta ** (-36 / 128), rel=1e-6)
    assert got[35] == pytest.approx(theta ** (-70 / 128) / 16, rel=1e-6)
    # the amplitude config.json states is YaRN's 0.1 ln(factor) + 1
    assert yarn.attention_factor == pytest.approx(0.1 * math.log(16) + 1)
    np.testing.assert_allclose(
        reference.yarn(128, theta, 16, 8192, 32, 1), got, rtol=1e-6)


def test_rope_with_frequencies_and_amplitude_and_plain_as_it_was():
    x = jax.random.normal(jax.random.key(0), (1, 2, 64, 16))
    inv = np.linspace(1.0, 0.01, 8).astype(np.float32)
    got = tfm._rope(x, 0.0, inv, 1.5)
    angles = np.arange(64)[:, None] * inv[None, :]
    x1, x2 = np.asarray(x[..., :8]), np.asarray(x[..., 8:])
    want = 1.5 * np.concatenate(
        [x1 * np.cos(angles) - x2 * np.sin(angles),
         x2 * np.cos(angles) + x1 * np.sin(angles)], -1)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4)
    # a full layer's positions in the program are the reference's
    family = _family(jnp.float32, layers=[3])
    inv_freq, amplitude = reference.positions(family.spec, afmoe.FULL)
    np.testing.assert_allclose(
        np.asarray(mellum._rotary(x, family.cfg, afmoe.FULL)),
        np.asarray(reference.rotary(x, inv_freq, amplitude)), atol=1e-6)
    assert amplitude == family.cfg.yarn.attention_factor != 1.0

    # existing callers: the same jaxpr as the function had before it
    # learnt either argument
    def as_it_was(x, theta):
        half = x.shape[-1] // 2
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
        angles = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] \
            * freqs[None, :]
        cos, sin = jnp.cos(angles), jnp.sin(angles)
        x32 = x.astype(jnp.float32)
        x1, x2 = x32[..., :half], x32[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x2 * cos + x1 * sin], axis=-1).astype(x.dtype)
    xb = x.astype(jnp.bfloat16)
    assert str(jax.make_jaxpr(lambda x: tfm._rope(x, 10000.0))(xb)) == str(
        jax.make_jaxpr(lambda x: as_it_was(x, 10000.0))(xb))


def _forward_hidden_as_it_was(params, tokens, cfg, sel=None,
                              with_routing=False):
    """`afmoe.forward_hidden` as PR 35's tree (749d185) had it, before it
    learnt to scan another family's layer: no `layer`, no `embed`."""
    import functools

    from jax import lax
    x = afmoe._embed(params, tokens, cfg)
    routings = None
    for key, kinds, periods in afmoe._stack_plan(cfg):
        is_moe = key == "moe"
        p = len(kinds)
        stacked = jax.tree.map(
            lambda a: a.reshape(periods, p, *a.shape[1:]), params[key])
        sels = None
        if is_moe and sel is not None:
            sels = sel.reshape(periods, p, *sel.shape[1:])

        def period(x, xs, kinds=kinds, is_moe=is_moe):
            lps, sels = xs
            lps = afmoe._unstack(lps, len(kinds))
            routed = []
            for i, kind in enumerate(kinds):
                layer = afmoe._remat(functools.partial(
                    afmoe._layer, cfg=cfg, kind=kind, is_moe=is_moe), cfg)
                x, r = layer(x, lps[i], None if sels is None else sels[i])
                routed.append(r)
            if is_moe and with_routing:
                return x, jax.tree.map(lambda *a: jnp.stack(a), *routed)
            return x, None

        x, r = lax.scan(period, x, (stacked, sels))
        if is_moe and with_routing:
            routings = jax.tree.map(
                lambda a: a.reshape(periods * p, *a.shape[2:]), r)
    x = tfm._rms_norm(x, params["final_ln"], None, eps=cfg.rms_norm_eps)
    return (x, routings) if with_routing else x


def test_trinity_minis_tiny_lowered_step_is_the_text_it_was():
    """The hooks `afmoe.forward_hidden` gained for another family's layer
    leave trinity-mini's own step alone: the cell's train step at tiny
    widths (value_and_grad of the family's loss + adamw) lowers to the
    same text as with the function PR 35 had, written out above.  Both
    texts come from this process, so the check holds whatever jax prints."""
    from benchmark.families import afmoe as family_afmoe
    from benchmark.tests import tiny_afmoe
    config = tiny_afmoe.config()
    family = family_afmoe.Family(config, config["job"])
    params = jax.eval_shape(family.init, jax.random.key(0))
    batch = jax.eval_shape(lambda k: family.make_batch(k, 2),
                           jax.random.key(0))
    opt = family.optimizer()
    state = jax.eval_shape(opt.init, params)

    def lowered(loss):
        def step(p, s, b):
            value, g = jax.value_and_grad(loss)(p, b)
            u, s = opt.update(g, s, p)
            return optax.apply_updates(p, u), s, value
        return jax.jit(step).lower(params, state, batch).as_text()

    def loss_as_it_was(p, b):
        return afmoe.loss_fn(p, b, family.cfg,
                             hidden=_forward_hidden_as_it_was)
    now, was = lowered(family.loss), lowered(loss_as_it_was)
    assert len(now) > 1_000_000        # a whole train step, not a stub
    assert now == was


def test_the_step_names_its_scopes_and_its_windowed_calls():
    """What the device trace is read by: the three scopes, and the
    sliding layers' kernels named after their window."""
    family = _family(layers=[2, 3])
    params = jax.eval_shape(family.init, jax.random.key(0))
    batch = jax.eval_shape(lambda k: family.make_batch(k, 1),
                           jax.random.key(0))
    text = jax.jit(jax.grad(family.loss)).lower(params, batch).as_text(
        debug_info=True)
    for scope in ("mellum.attn.sliding_attention",
                  "mellum.attn.full_attention", "mellum.moe"):
        assert scope in text, scope
    jaxpr = str(jax.make_jaxpr(jax.grad(family.loss))(params, batch))
    assert all(f"flash_{kind}_w128" in jaxpr
               for kind in ("fwd", "dq", "dkv"))


def test_model_flops_and_parameters_at_the_published_widths():
    """The configuration file's own count: 595,154,176 parameters, and
    the FLOPs a token the issue reckoned (about 2.1 GFLOP)."""
    import json
    import os

    from benchmark.harness import manifest
    with open(os.path.join(manifest.BENCH, "configs",
                           "mellum2-12b-a2.5b-instruct.json")) as f:
        config = json.load(f)
    family = family_mellum.Family(config, config["job"])
    shapes = jax.eval_shape(family.init, jax.random.key(0))
    count = sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))
    assert count == 595_154_176
    assert f"{count:,}" in config["deployment"]["parameters"]
    per_token = family_mellum.matmul_params_per_token(
        config["published"], 4, 16, 24576)
    assert per_token == 4 * (21_233_664 + 147_456 + 2 * 6_193_152) \
        + 56_623_104
    flops = family.model_flops_per_sample() / family.seq_len
    assert 2.0e9 < flops < 2.2e9
    # the buffer of the expert layer at the cell's batch
    assert family.cfg.moe.buffer_rows(32768) == int(
        65536 * config["program_options"]["pinned"]["moe_capacity_factor"])


def test_the_new_code_stays_out_of_the_other_cells_imports():
    """The afmoe, gpt2, vgg and granitehybrid families import nothing of
    the mellum model (a PR was once refused on another cell's set-up
    time)."""
    import subprocess
    import sys
    from testutil import cpu_env
    code = ("import sys, byteps_tpu, byteps_tpu.models.afmoe, "
            "benchmark.families.afmoe, benchmark.families.gpt2, "
            "benchmark.families.vgg, benchmark.families.granitehybrid, "
            "benchmark.jobs.ingraph; "
            "bad = [m for m in sys.modules if 'mellum' in m]"
            "; assert not bad, bad")
    r = subprocess.run([sys.executable, "-c", code], env=cpu_env(),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]


def test_init_is_the_programs_own_and_the_config_has_no_knob_for_it():
    """Normal / sqrt(fan_in) in every matrix, the embedding's rows
    included, and every norm scale 1; `MellumConfig` has the published
    fields and the program's options, none that sizes an initial weight
    (one was tried for the benchmark's sake and went, PERF.md, PR 36).
    What the CELL starts from is the benchmark's business: its family
    takes the program's weights and makes the embedding's rows
    `initial_weights.embed_rows_times` as large, nothing else touched."""
    family = _family(jnp.float32)
    assert not [f.name for f in dataclasses.fields(family.cfg)
                if "init" in f.name]
    params = mellum.init_params(jax.random.key(0), family.cfg)
    D = family.cfg.hidden_size
    fan_in = {"embed": D, "head": D, "qkv_w": D, "router_w": D,
              "expert_gate_w": D, "expert_up_w": D,
              "attn_out_w": family.cfg.num_heads * family.cfg.head_dim,
              "expert_down_w": family.cfg.moe_intermediate_size}
    leaves = {**params, **params["moe"]}
    for name, n in fan_in.items():
        assert float(jnp.std(leaves[name])) * math.sqrt(n) == pytest.approx(
            1.0, rel=0.1), name
    for name in ("final_ln", "input_ln", "post_attn_ln", "q_norm", "k_norm"):
        assert np.all(np.asarray(leaves[name]) == 1.0), name

    times = tiny_mellum.config()["initial_weights"]["embed_rows_times"]
    assert times == 64
    cells = family.init(jax.random.key(0))
    np.testing.assert_allclose(np.asarray(cells.pop("embed")),
                               times * np.asarray(params.pop("embed")),
                               rtol=1e-6)
    jax.tree.map(np.testing.assert_array_equal, cells, params)
