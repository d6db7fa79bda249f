"""The sdar model (`byteps_tpu/models/sdar.py`): the parameter count at
the published widths, a batch's noise against the rule, a loss that reads
no clean row, the share test that ties one chip's routed part to the
whole layer, the remat option that keeps the flash call's results, and
what the configuration refuses.  The program against its plain float32
reference (`benchmark/reference/sdarmoe.py`), loss and every gradient
leaf: `test_sdar_reference.py` and the unbroken case of
`test_sdar_variants.py`."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import sdarmoe as family_sdarmoe
from benchmark.harness import manifest
from benchmark.reference import sdarmoe as reference
from benchmark.tests import tiny_sdarmoe
from byteps_tpu.models import afmoe, sdar
from byteps_tpu.parallel import dropless_moe
from family_cases import Cases

CASES = Cases(tiny_sdarmoe, family_sdarmoe.Family)
CELL = "sdar-30b-a3b-chat.ingraph-1chip"


def test_parameter_count_at_the_published_widths():
    """Counted from the tree the cell's family builds (shapes alone): a
    layer 18,874,368 (attention) + 256 (q / k norms) + 4,096 (two norms) +
    262,144 (router) + 16 x 4,718,592 (held experts) = 94,638,336; six of
    them, embedding and head of 18,992 rows, the last norm."""
    cell = manifest.load_cell(CELL)
    family = family_sdarmoe.Family(cell.config, cell.job)
    tree = jax.eval_shape(family.init, jax.random.key(0))
    layer = sum(int(np.prod(leaf.shape[1:]))
                for leaf in jax.tree.leaves(tree["moe"]))
    assert layer == 18_874_368 + 256 + 4_096 + 262_144 + 16 * 4_718_592
    assert layer == 94_638_336
    total = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))
    assert total == 6 * layer + 2 * 38_895_616 + 2_048 == 645_623_296
    assert family.cfg.mask_token == 18_991
    assert all(leaf.dtype == jnp.float32 for leaf in jax.tree.leaves(tree))


def test_a_batch_follows_the_noise_rule():
    cfg = CASES.family(jnp.float32, layers=[0]).cfg
    beta, eps = cfg.block_length, cfg.noise_eps
    tokens, masked, weight = sdar.synthetic_batch(jax.random.key(5), 4, 512,
                                                  cfg)
    assert tokens.dtype == jnp.int32 and masked.dtype == jnp.bool_
    assert weight.dtype == jnp.float32 and weight.shape == (4, 512)
    # the data never draws the mask token, the slice's last id
    assert int(tokens.min()) >= cfg.vocab_start
    assert int(tokens.max()) < cfg.mask_token == (cfg.vocab_start
                                                  + cfg.vocab_size - 1)
    w, m = np.asarray(weight), np.asarray(masked)
    assert (w[~m] == 0).all() and (w[m] >= 1.0).all() and w.max() <= 1 / eps
    # one t a block: its masked tokens carry one and the same 1 / t
    blocks_w, blocks_m = w.reshape(4, -1, beta), m.reshape(4, -1, beta)
    top = blocks_w.max(-1, keepdims=True)
    assert (np.where(blocks_m, blocks_w, top) == top).all()
    # masked with probability t: the share follows t = 1 / weight
    t = 1.0 / top[..., 0][blocks_m.any(-1)]
    share = blocks_m.mean(-1)[blocks_m.any(-1)]
    assert abs(np.corrcoef(t, share)[0, 1]) > 0.5
    counters = jax.tree.map(float, sdar.batch_counters((tokens, masked,
                                                        weight)))
    assert 0.4 < counters["masked_share"] < 0.6
    assert 0.8 < counters["weight_mean"] < 1.25
    sdar.record_batch(counters)
    import byteps_tpu as bps
    assert bps.get_metrics()["bps_bd_masked_share"] == pytest.approx(
        counters["masked_share"])
    with pytest.raises(ValueError):
        sdar.synthetic_batch(jax.random.key(0), 1, 130, cfg)


def test_the_two_copies_and_their_positions():
    family = CASES.family(jnp.float32, layers=[0])
    cfg = family.cfg
    params, batch = CASES.operands(family)
    tokens, masked, _ = batch
    x, positions = sdar.two_copies(params, batch, cfg)
    L = tokens.shape[1]
    assert x.shape == (tokens.shape[0], 2 * L, cfg.hidden_size)
    np.testing.assert_array_equal(np.asarray(positions),
                                  np.arange(2 * L) % L)
    np.testing.assert_array_equal(np.asarray(x[:, :L]),
                                  np.asarray(params["embed"][tokens]))
    mask_row = params["embed"][cfg.mask_token - cfg.vocab_start]
    noised = np.asarray(x[:, L:])
    assert (noised[np.asarray(masked)] == np.asarray(mask_row)).all()
    assert (noised[~np.asarray(masked)]
            == np.asarray(x[:, :L])[~np.asarray(masked)]).all()


def test_the_loss_reads_no_clean_row():
    """What the last layer adds to the clean rows reaches nothing: the
    gradient with respect to a perturbation of those rows is zero, and
    that of the noised rows is not; streamed head and plain head agree."""
    family = CASES.family(jnp.float32, layers=[0])
    cfg = family.cfg
    params, batch = CASES.operands(family)
    x, _ = sdar.run_rows(params, batch, cfg)
    L = batch[0].shape[1]

    def loss(delta, cfg=cfg):
        return sdar.head_loss(params, x + delta, batch, cfg)
    grad = jax.grad(loss)(jnp.zeros_like(x))
    assert not np.asarray(grad[:, :L]).any()
    assert np.abs(np.asarray(grad[:, L:])).max() > 0
    # only the masked tokens' rows carry weight
    per_row = np.abs(np.asarray(grad[:, L:])).sum(-1)
    assert (per_row[~np.asarray(batch[1])] == 0).all()
    plain = dataclasses.replace(cfg, ce_chunk_rows=0)
    np.testing.assert_allclose(float(loss(0.0)), float(loss(0.0, plain)),
                               rtol=1e-6)


def test_keeping_the_flash_calls_results_changes_no_number():
    """`remat_policy="kernels"` (the flash call's `o` and `lse` and the
    router's choice kept by name) against whole-layer remat."""
    family = CASES.family(jnp.float32, layers=[0])
    params, batch = CASES.operands(family)
    kept = dataclasses.replace(family.cfg, remat_policy="kernels")
    step = jax.jit(jax.value_and_grad(sdar.loss_fn), static_argnums=2)
    got, want = step(params, batch, kept), step(params, batch, family.cfg)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-7)


def test_the_shares_add_up_to_the_layer():
    """Guide, section 4: over the eight chips that share a layer, the
    routed parts the shares compute (there is no shared expert to count
    once) are the uncut reference's expert layer, for the same rows, every
    pair on exactly one chip; and the eight slices' logits laid side by
    side are the whole head's."""
    family = CASES.family(jnp.float32, layers=[0])
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
    for chip in range(8):
        held = tuple(range(chip * E // 8, (chip + 1) * E // 8))
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
    V = 8 * 40
    head = jax.random.normal(k[0], (V, D))
    x = jax.random.normal(k[1], (2, 16, D))
    side_by_side = jnp.concatenate(
        [afmoe.head_logits(x, head[c * 40:(c + 1) * 40]) for c in range(8)],
        axis=-1)
    np.testing.assert_allclose(np.asarray(side_by_side),
                               np.asarray(x @ head.T), atol=1e-4, rtol=1e-5)


def test_what_the_configuration_refuses():
    cfg = CASES.family(jnp.float32, layers=[0]).cfg
    with pytest.raises(ValueError):          # the mask is never an array
        dataclasses.replace(cfg, attn_impl="dense")
    with pytest.raises(ValueError):
        dataclasses.replace(cfg, block_length=0)
    with pytest.raises(ValueError):          # at first use
        afmoe._remat(lambda x: x, dataclasses.replace(
            cfg, remat_policy="no_such"))
    assert cfg.layer_types == (afmoe.BLOCK_DIFFUSION,)
    assert afmoe._stack_plan(dataclasses.replace(cfg, num_layers=6)) == [
        ("moe", (afmoe.BLOCK_DIFFUSION,), 6)]
    # a block of 5 tokens does not divide a tile of 128
    odd = dataclasses.replace(cfg, block_length=5)
    params = jax.eval_shape(lambda k: sdar.init_params(k, odd),
                            jax.random.key(0))
    batch = jax.eval_shape(
        lambda k: sdar.synthetic_batch(k, 1, 640, odd), jax.random.key(0))
    with pytest.raises(ValueError):
        jax.eval_shape(lambda p, b: sdar.loss_fn(p, b, odd), params, batch)
