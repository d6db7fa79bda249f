"""The afmoe model (`byteps_tpu/models/afmoe.py`) at tiny widths against
its plain float32 reference (`benchmark/reference/afmoe.py`), through the
benchmark's own family and comparison: loss and every gradient leaf, the
choice of experts apart from the arithmetic (the seven broken variants:
`test_afmoe_variants.py`), and the test that ties one chip's share to the
whole model."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families import afmoe as family_afmoe
from benchmark.harness import seeded
from benchmark.reference import afmoe as reference
from benchmark.tests import tiny_afmoe
from byteps_tpu.models import afmoe
from byteps_tpu.parallel import dropless_moe
from family_cases import Cases

CASES = Cases(tiny_afmoe, family_afmoe.Family)
_family = CASES.family


def test_expert_bias_moves_the_choice_and_not_the_weights():
    family = _family(jnp.float32, tiny_afmoe.FLOAT32, layers=[4])
    params = seeded.params(family, 1)
    tokens = seeded.batch(family, 1, 1)[0]
    plain = afmoe.routing(params, tokens, family.cfg)
    params["moe"]["expert_bias"] = jnp.zeros(
        (1, family.cfg.num_experts)).at[0, 5].set(10.0)
    biased = afmoe.routing(params, tokens, family.cfg)
    assert bool((biased.sel == 5).any(-1).all())
    assert not bool((plain.sel == 5).any(-1).all())
    np.testing.assert_allclose(np.asarray(biased.weights.sum(-1)),
                               family.cfg.route_scale, rtol=1e-5)
    # and the reference reads the same leaf the same way
    batch = seeded.batch(family, 1, 1)
    np.testing.assert_allclose(
        float(family.loss(params, batch)),
        float(reference.loss(params, batch, family.spec)), rtol=1e-5)


def test_the_shares_add_up_to_the_model():
    """Guide, section 4: over the eight chips that share a layer, the
    routed parts the shares compute plus the shared expert counted once
    are the uncut reference's expert layer, for the same tokens; and the
    eight slices' logits laid side by side are the whole head's."""
    family = _family(jnp.float32, layers=[4])
    cfg, spec = family.cfg, family.spec
    E, D, F = cfg.num_experts, cfg.hidden_size, cfg.moe_intermediate_size
    k = jax.random.split(jax.random.key(0), 8)
    whole = {
        "router_w": jax.random.normal(k[0], (D, E)) / 8,
        "expert_gate_w": jax.random.normal(k[1], (E, D, F)) / 8,
        "expert_up_w": jax.random.normal(k[2], (E, D, F)) / 8,
        "expert_down_w": jax.random.normal(k[3], (E, F, D)) / 6,
        "shared_gate_w": jax.random.normal(k[4], (D, F)) / 8,
        "shared_up_w": jax.random.normal(k[5], (D, F)) / 8,
        "shared_down_w": jax.random.normal(k[6], (F, D)) / 6,
    }
    m = jax.random.normal(k[7], (192, D))
    with jax.default_matmul_precision("highest"):
        uncut, _ = reference.experts_layer(
            m, whole, {**spec, "held": tuple(range(E))})
        shared = reference.swiglu(m, whole["shared_gate_w"],
                                  whole["shared_up_w"],
                                  whole["shared_down_w"])
    total, rows = shared, 0
    for chip in range(8):
        held = tuple(range(chip * E // 8, (chip + 1) * E // 8))
        moe = dataclasses.replace(cfg.moe, held=held)
        experts = {n: whole["expert_" + n][jnp.asarray(held)]
                   for n in ("gate_w", "up_w", "down_w")}
        part, routing = dropless_moe.held_experts(m, whole["router_w"],
                                                  experts, moe)
        total, rows = total + part, rows + int(routing.held_rows)
    assert rows == m.shape[0] * cfg.num_experts_per_tok
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=2e-5, rtol=2e-5)

    V = 8 * 40
    head = jax.random.normal(k[0], (V, D))
    x = jax.random.normal(k[1], (2, 16, D))
    side_by_side = jnp.concatenate(
        [afmoe.head_logits(x, head[c * 40:(c + 1) * 40]) for c in range(8)],
        axis=-1)
    np.testing.assert_allclose(np.asarray(side_by_side),
                               np.asarray(x @ head.T), atol=1e-4, rtol=1e-5)


def test_a_slice_that_starts_elsewhere():
    """The second chip's slice of the vocabulary: ids from `vocab_start`,
    the same loss as the first chip's on the same rows."""
    family = _family(jnp.float32, layers=[1])
    params = seeded.params(family, 2)
    tokens, targets = seeded.batch(family, 2, 1)
    first = family.loss(params, (tokens, targets))
    start = family.cfg.vocab_size
    moved = dataclasses.replace(family.cfg, vocab_start=start)
    second = afmoe.loss_fn(params, (tokens + start, targets + start), moved)
    assert float(first) == float(second)
    batch = afmoe.synthetic_batch(jax.random.key(0), 2, 8, moved)
    assert int(batch[0].min()) >= start
    assert int(batch[0].max()) < 2 * start


def test_stack_plan_scans_whole_periods():
    def plan(types, dense):
        cfg = dataclasses.replace(_family().cfg, layer_types=tuple(types),
                                  num_dense_layers=dense)
        return [(k, len(kinds), n) for k, kinds, n in afmoe._stack_plan(cfg)]
    s, f = afmoe.SLIDING, afmoe.FULL
    assert plan([s, s, s, s, f], 1) == [("dense", 1, 1), ("moe", 4, 1)]
    assert plan([s, s] + [s, s, s, f] * 3, 2) == [("dense", 1, 2),
                                                  ("moe", 4, 3)]
    # the published 2 + 30: the expert layers start mid-period
    published = ([s, s, s, f] * 8)
    assert plan(published, 2) == [("dense", 1, 2), ("moe", 30, 1)]


def test_model_flops_count_what_each_layer_kind_needs():
    assert family_afmoe.window_pairs(8, None) == 36
    assert family_afmoe.window_pairs(8, 3) == 6 + 5 * 3
    assert family_afmoe.window_pairs(8, 100) == 36
    n = dict(hidden_size=8, head_dim=4, num_attention_heads=4,
             num_key_value_heads=2, moe_intermediate_size=16,
             intermediate_size=32, num_experts=16, num_experts_per_tok=4,
             num_shared_experts=1)
    attn = 8 * (2 * 4 + 2 * 2) * 4 + 4 * 4 * 8
    expert = 3 * 8 * 16
    got = family_afmoe.matmul_params_per_token(
        n, ["a", "b", "c"], dense_layers=1, held_experts=8, held_vocab=100)
    # 4 choices a token, half the experts held: two experts' worth
    assert got == 3 * attn + 3 * 8 * 32 + 2 * (8 * 16 + expert * (1 + 2)) \
        + 100 * 8


def test_gpt2_tiny_is_bit_equal_with_and_without_the_window_argument():
    """GPT-2's path through the shared flash adapter and kernel: the
    lowered program, the loss and every gradient are the same whether the
    adapter is today's (which can pass a window) or the call as it was
    before the kernel learnt one."""
    from byteps_tpu.models import transformer as tfm
    from byteps_tpu.ops.flash_attention import flash_attention

    def as_it_was(q, k, v, causal):
        B, H, S, Dh = q.shape
        block = tfm.flash_auto_block(S)
        out = flash_attention(*(t.reshape(B * H, S, Dh) for t in (q, k, v)),
                              causal, None, block, block)
        return out.reshape(B, H, S, Dh)

    cfg = tfm.get_config("tiny", attn_impl="flash", ce_chunk_rows=64)
    params = tfm.init_params(jax.random.key(0), cfg)
    batch = tfm.synthetic_batch(jax.random.key(1), 2, 128, cfg)
    now = jax.jit(jax.value_and_grad(
        lambda p, b: tfm.loss_fn(p, b, cfg)))
    before = jax.jit(jax.value_and_grad(
        lambda p, b: tfm.loss_fn(p, b, cfg, attn_fn=as_it_was)))
    assert now.lower(params, batch).as_text() == before.lower(
        params, batch).as_text()
    for a, b in zip(jax.tree.leaves(now(params, batch)),
                    jax.tree.leaves(before(params, batch))):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_the_new_code_stays_out_of_the_other_cells_imports():
    """`import byteps_tpu`, the transformer and the gpt2 and vgg families
    import nothing of the afmoe model or the dropless layer (a PR was once
    refused on another cell's set-up time)."""
    import subprocess
    import sys
    from testutil import cpu_env
    code = ("import sys, byteps_tpu, byteps_tpu.models, "
            "byteps_tpu.models.transformer, benchmark.families.gpt2, "
            "benchmark.families.vgg, benchmark.jobs.ingraph, "
            "benchmark.jobs.ps_joint; "
            "bad = [m for m in sys.modules if 'afmoe' in m or 'dropless' in m]"
            "; assert not bad, bad")
    r = subprocess.run([sys.executable, "-c", code], env=cpu_env(),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]


def test_post_attn_norm_init_evens_routing():
    """`post_attn_norm_init` sets where the scale of the norm after the
    attention starts; small, the stretches of a sequence that a flat
    attention makes alike no longer go to the same experts (float32, one
    expert layer behind a sliding one; the chip's numbers are in
    PERF.md)."""
    def worst_load(scale):
        family = _family(jnp.float32, layers=[1, 4])
        cfg = dataclasses.replace(family.cfg, post_attn_norm_init=scale)
        params = afmoe.init_params(jax.random.key(0), cfg)
        assert float(params["moe"]["post_attn_ln"][0, 0]) == np.float32(scale)
        assert float(params["moe"]["pre_mlp_ln"][0, 0]) == 1.0
        tokens = afmoe.synthetic_batch(jax.random.key(1), 8, 256, cfg)[0]
        counts = np.bincount(
            np.asarray(afmoe.routing(params, tokens, cfg).sel).ravel(),
            minlength=cfg.num_experts)
        return counts.max() / counts.mean()
    flat, small = worst_load(1.0), worst_load(0.1)
    assert flat > 1.3 * small, (flat, small)
