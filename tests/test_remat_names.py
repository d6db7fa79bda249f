"""The names a rematerialised layer can keep (`checkpoint_name`):
`flash_attention.KEPT_NAME` over a flash call's `o` and `lse`,
`granite_hybrid.IN_PROJ_NAME` over `in_proj`'s result,
`dropless_moe.ROUTING_NAME` over what the router decided and the plan
sorted.  A policy that lists a name keeps its values and the recompute does
not make them again; under every other policy the name is inert; either
way the gradients are the same bits, because a kept value is the value the
forward pass made.  Counted in jaxprs, a call for each time it runs
(`testutil.eqns`).  `tests/test_nemotron_h.py` counts the nemotron_h step,
`tests/test_keye.py` holds the other decoders' lowered steps to the text
they had; a file of its own, so that its compiles run on a worker of their
own."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import byteps_tpu as bps
from benchmark.families import afmoe as family_afmoe
from benchmark.families import gpt2 as family_gpt2
from benchmark.harness import seeded
from benchmark.tests import (tiny_afmoe, tiny_granitehybrid, tiny_keye,
                             tiny_nemotronh)
from byteps_tpu.models import granite_hybrid, nemotron_h
from byteps_tpu.ops import flash_attention as fa
from byteps_tpu.ops import short_conv
from byteps_tpu.parallel import dropless_moe
from testutil import (eqns, is_flash_forward, is_product, named_bytes,
                      tiny_gpt2_config)

keep_only = jax.checkpoint_policies.save_only_these_names


def _count(fn, args, found):
    return sum(map(found, eqns(jax.make_jaxpr(fn)(*args).jaxpr)))


def _same_bits(a, b):
    a, b = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(a) == len(b) and any(np.asarray(t).any() for t in a)
    for mine, theirs in zip(a, b):
        assert mine.dtype == theirs.dtype
        np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs))


# ---------------------------------------------------------------------------
# The three places that make the values, each alone
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("streaming", [False, True],
                         ids=["resident", "streaming"])
@pytest.mark.parametrize("window", [None, 128], ids=["full", "window"])
def test_a_flash_call_kept_by_name_runs_its_forward_kernel_once(streaming,
                                                                window):
    """`o` and `lse` kept: the differentiated call under the policy holds
    one forward kernel, under a plain `jax.checkpoint` two, with no
    checkpoint one; dq, dk and dv are the same bits in all three."""
    q, k, v, g = (jax.random.normal(key, (2, 256, 16))
                  for key in jax.random.split(jax.random.key(0), 4))

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, True, None, 128, 128, None,
                                 streaming, window)
        return (jnp.tanh(out) * g).sum()
    walks = {"kept": jax.checkpoint(loss, policy=keep_only(fa.KEPT_NAME)),
             "plain": jax.checkpoint(loss), "whole": loss}
    grads = {n: jax.grad(f, (0, 1, 2)) for n, f in walks.items()}
    assert {n: _count(f, (q, k, v), is_flash_forward)
            for n, f in grads.items()} == {"kept": 1, "plain": 2, "whole": 1}
    kept, plain, whole = (jax.jit(grads[n])(q, k, v)
                          for n in ("kept", "plain", "whole"))
    _same_bits(kept, plain)
    _same_bits(kept, whole)


def _expert_layer(hold_held_weight):
    D, F, E, T = 16, 24, 8, 96
    k = jax.random.split(jax.random.key(3), 5)
    cfg = dropless_moe.MoEConfig(
        num_experts=E, top_k=2, held=(1, 4, 6), row_multiple=8,
        hold_held_weight=hold_held_weight)
    x = jax.random.normal(k[0], (T, D))
    g = jax.random.normal(k[4], (T, D))
    operands = (x, jax.random.normal(k[1], (D, E)),
                {"up_w": jax.random.normal(k[2], (3, D, F)) / 4,
                 "down_w": jax.random.normal(k[3], (3, F, D)) / 5})

    def loss(x, router_w, experts):
        out, _ = dropless_moe.held_experts(x, router_w, experts, cfg)
        return (out * g).sum()
    return cfg, loss, operands


@pytest.mark.parametrize("hold_held_weight", [False, True],
                         ids=["every_weight_learns", "held_weight_held"])
def test_an_expert_layers_gradient_is_the_same_bits_whatever_encloses_it(
        hold_held_weight):
    """`held_experts` with no checkpoint around it, under a plain
    `jax.checkpoint` and under a policy that keeps `ROUTING_NAME`: the
    three gradients (tokens, router, experts) are the same to the bit, on
    a routing that passes the first buffer (the exact path's loop runs);
    and only under the policy do the score product, the top-k and the
    plan's two sorts (the pairs by expert, and each pair's place in that
    list, its inverse) run once."""
    cfg, loss, operands = _expert_layer(hold_held_weight)
    T, D = operands[0].shape
    assert cfg.sorted_rows(T) > cfg.buffer_rows(T)
    walks = {"kept": jax.checkpoint(
                 loss, policy=keep_only(dropless_moe.ROUTING_NAME)),
             "plain": jax.checkpoint(loss), "whole": loss}
    grads = {n: jax.grad(f, (0, 1, 2)) for n, f in walks.items()}
    made = [lambda e: is_product(e, (T, D), (D, cfg.num_experts)),
            lambda e: e.primitive.name == "top_k",
            lambda e: e.primitive.name == "sort"]
    assert {n: [_count(f, operands, m) for m in made]
            for n, f in grads.items()} == {
        "kept": [1, 1, 2], "plain": [2, 2, 4], "whole": [1, 1, 2]}
    kept, plain, whole = (jax.jit(grads[n])(*operands)
                          for n in ("kept", "plain", "whole"))
    _same_bits(kept, plain)
    _same_bits(kept, whole)


def test_what_the_routing_name_holds_is_what_the_configuration_reckons():
    cfg, loss, operands = _expert_layer(True)
    T = operands[0].shape[0]
    named = named_bytes(jax.make_jaxpr(loss)(*operands).jaxpr)
    assert named == {dropless_moe.ROUTING_NAME: cfg.kept_bytes(T)}
    # the cell's: 16,384 tokens, 6 of 128 experts, 8 held, a buffer of
    # 7,680 rows and the exact path's of 1,024
    cell = dropless_moe.MoEConfig(num_experts=128, top_k=6,
                                  held=tuple(range(8)))
    assert (cell.buffer_rows(16384), cell.past_rows(16384),
            cell.sorted_rows(16384)) == (7680, 1024, 98816)
    assert cell.kept_bytes(16384) == 9_963_584
    assert fa.kept_bytes(32, 16384, 128, jnp.bfloat16) == 136_314_880


# ---------------------------------------------------------------------------
# The two models that list names
# ---------------------------------------------------------------------------
def _step_bits(family, dtype):
    """Loss and gradients of the family's own step, compiled.  In
    bfloat16 the compiler may otherwise skip a rounding between two fused
    operations (`xla_allow_excess_precision`), differently in two
    programs that fuse differently: a recompute then differs from the
    forward pass it repeats by roundings, which is the compiler's doing
    and not the names'."""
    params, batch = seeded.params(family, 0), seeded.batch(family, 0, 1)
    options = ({} if dtype == jnp.float32
               else {"xla_allow_excess_precision": False})
    return jax.jit(jax.value_and_grad(family.loss)).lower(
        params, batch).compile(compiler_options=options)(params, batch)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_nemotron_h_step_is_the_same_bits_whatever_is_kept(monkeypatch,
                                                               dtype):
    """Loss and every leaf's gradient of `M*E` under the model's policy
    equal, bit for bit, those of the same walk under a plain
    `jax.checkpoint` (a policy of no name)."""
    family = tiny_nemotronh.family(dtype, layers=[4, 5, 6])
    assert len(nemotron_h.KEPT_NAMES) == 3
    kept = _step_bits(family, dtype)
    monkeypatch.setattr(nemotron_h, "KEPT_NAMES", ())
    _same_bits(kept, _step_bits(family, dtype))


def _granite_family(dtype, layers):
    config = tiny_granitehybrid.config(layers=layers)
    from benchmark.families import granitehybrid
    family = granitehybrid.Family(config, config["job"])
    family.cfg = dataclasses.replace(family.cfg, dtype=dtype)
    return family


def test_the_granite_step_keeps_its_own_names(monkeypatch):
    """granite's policy is its own file's: of `attention, mamba` a step
    holds each kept value's maker once a layer (forward; a run's scan
    body is written once) and twice under a policy of no name; loss and
    gradients are the same bits; `bps_remat_kept_*` says what the layers
    hold."""
    family = _granite_family(jnp.float32, [5, 6])
    cfg = family.cfg
    assert cfg.layer_types == (granite_hybrid.ATTENTION,
                               granite_hybrid.MAMBA)
    params, batch = seeded.params(family, 0), seeded.batch(family, 0, 1)
    B, S, D = 1, family.seq_len, cfg.hidden_size
    wide = cfg.d_inner + cfg.conv_dim + cfg.mamba_n_heads
    makers = {fa.KEPT_NAME: is_flash_forward,
              granite_hybrid.IN_PROJ_NAME:
                  lambda e: is_product(e, (B, S, D), (D, wide)),
              # the convolution's kernel keeps nothing of its own: its
              # residual is a slice of `in_proj`'s kept result, made again
              # with the forward call whatever the policy lists
              "mamba.conv": lambda e: (
                  e.primitive.name == "pallas_call"
                  and e.params["name"] == short_conv.MAMBA_FWD_NAME)}

    def made():
        return {name: _count(jax.grad(family.loss), (params, batch), maker)
                for name, maker in makers.items()}
    assert made() == {name: 1 if name in granite_hybrid.KEPT_NAMES else 2
                      for name in makers}
    named = named_bytes(jax.make_jaxpr(family.loss)(params, batch).jaxpr)
    assert set(named) == set(granite_hybrid.KEPT_NAMES)
    metrics = bps.get_metrics()
    for name in granite_hybrid.KEPT_NAMES:
        assert metrics[f'bps_remat_kept_layers{{name="{name}"}}'] == 1
        assert metrics[f'bps_remat_kept_bytes{{name="{name}"}}'] == named[
            name]
    kept = _step_bits(family, jnp.float32)
    monkeypatch.setattr(granite_hybrid, "KEPT_NAMES", ())
    assert made() == dict.fromkeys(makers, 2)
    _same_bits(kept, _step_bits(family, jnp.float32))


# ---------------------------------------------------------------------------
# The steps that list none of the names: inert
# ---------------------------------------------------------------------------
def _gpt2():
    config = tiny_gpt2_config()
    return family_gpt2.Family(config, config["job"])


def _afmoe():
    config = tiny_afmoe.config()
    return family_afmoe.Family(config, config["job"])


INERT = {
    # family, its remat policy, the kernels counted: flash forward calls
    # and, where it has expert layers, their sorts; keye's own forward
    # kernel, which ITS policy keeps
    "gpt2": (_gpt2, "none", {"flash_forward": 2}),
    "afmoe": (_afmoe, "none", {"flash_forward": 2, "sort": 2,
                                          "top_k": 2}),
    "keye": (lambda: tiny_keye.family(layers=[0, 1]), "selection",
             {"sort": 2, "top_k": 2, "sparse_fwd": 1}),
}
FOUND = {
    "flash_forward": is_flash_forward,
    "sort": lambda e: e.primitive.name == "sort",
    "top_k": lambda e: e.primitive.name == "top_k",
    "sparse_fwd": lambda e: (e.primitive.name == "pallas_call"
                             and e.params["name"] == "sparse_fwd"),
}


@pytest.mark.parametrize("name", INERT)
def test_the_names_are_inert_where_no_policy_lists_them(name):
    """gpt2's transformer and afmoe under the remat policy "none", keye
    under "selection" (which lists the sparse call's names and none of
    these): a step makes everything a name covers as often as it did,
    twice what the forward pass alone does, a layer."""
    make, policy, times = INERT[name]
    family = make()
    assert family.cfg.remat and family.cfg.remat_policy == policy
    params = jax.eval_shape(family.init, jax.random.key(0))
    batch = jax.eval_shape(lambda k: family.make_batch(k, 1),
                           jax.random.key(0))
    for kernel, again in times.items():
        forward = _count(family.loss, (params, batch), FOUND[kernel])
        step = _count(jax.grad(family.loss), (params, batch), FOUND[kernel])
        assert forward > 0 and step == again * forward, (kernel, forward,
                                                         step)
