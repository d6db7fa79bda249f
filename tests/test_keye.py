"""The keye model (`byteps_tpu/models/keye.py`) and its attention over
selected keys (`byteps_tpu/ops/sparse_attention.py`) at tiny widths
against the plain float32 reference (`benchmark/reference/keye.py`),
through the benchmark's own family and comparison: loss and every gradient
leaf, a share and the whole model, both discontinuous choices apart from
the arithmetic (the ten broken variants: `test_keye_variants.py`), the
selection exact against a sort with ties, short rows against dense attention, sectioned rotary
positions, the eight shares against the uncut layer, and the machinery
shared with `afmoe.py` and `mellum.py` left as it was."""

import dataclasses
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import afmoe as family_afmoe
from benchmark.families import gpt2 as family_gpt2
from benchmark.families import keye as family_keye
from benchmark.families import mellum as family_mellum
from benchmark.harness import manifest, seeded
from benchmark.reference import keye as reference
from benchmark.tests import tiny_afmoe, tiny_keye, tiny_mellum
from byteps_tpu.models import afmoe, keye
from byteps_tpu.models import transformer as tfm
from byteps_tpu.ops import sparse_attention as sa
from byteps_tpu.parallel import dropless_moe
from family_cases import Cases
from testutil import tiny_gpt2_config

CASES = Cases(tiny_keye)
_family = CASES.family


def test_selection_is_exact_against_a_sort_with_ties():
    """Integer-valued indexer operands make every sum exact and most
    rows' thresholds tied: the kernels' mask is the stable sort's first
    `topk` of the visible keys in every row, and the forward kernel's
    counter says min(t + 1, topk)."""
    B, H, Hkv, S, D, J, Di, topk = 1, 4, 2, 512, 32, 3, 16, 64
    k = jax.random.split(jax.random.key(0), 6)
    q = jax.random.normal(k[0], (B, H, S, D))
    kk, v = (jax.random.normal(k[i], (B, Hkv, S, D)) for i in (1, 2))
    qi = jnp.round(jax.random.normal(k[3], (B, J, S, Di)))
    ki = jnp.round(jax.random.normal(k[4], (B, S, Di)))
    w = jnp.round(2 * jax.random.normal(k[5], (B, S, J)))
    kit = ki.transpose(0, 2, 1)
    aux = sa.select(qi, kit, w, topk, 128)
    keep = np.asarray(sa.keep_mask(qi, kit, aux, 128, 128))[0].astype(bool)
    scores = np.asarray(sa.index_scores(qi, ki, w))[0]
    want = np.zeros((S, S), bool)
    tied = 0
    for t in range(S):
        n = min(t + 1, topk)
        order = np.argsort(-scores[t, :t + 1], kind="stable")[:n]
        want[t, order] = True
        tied += (scores[t, :t + 1] == scores[t, order[-1]]).sum() > 1
    assert tied > S // 2
    assert (keep == want).all()
    assert (np.asarray(sa.dense_keep(qi, ki, w, topk))[0] == want).all()
    out, kept = sa.selected_attention(q, kk, v, qi, ki, w, topk, 128, 128)
    assert (np.asarray(kept)[0] == np.minimum(np.arange(S) + 1, topk)).all()
    dense, kept_dense = sa.selected_attention_dense(q, kk, v, qi, ki, w, topk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), atol=2e-5)
    assert (np.asarray(kept_dense) == np.asarray(kept)).all()
    # the backward pass holds the selection constant
    g = jax.random.normal(k[0], out.shape)
    grads = [jax.grad(lambda *a: (f(*a, qi, ki, w, topk)[0] * g).sum(),
                      (0, 1, 2))(q, kk, v)
             for f in (lambda *a: sa.selected_attention(*a, 128, 128),
                       sa.selected_attention_dense)]
    for mine, plain in zip(*grads):
        np.testing.assert_allclose(np.asarray(mine), np.asarray(plain),
                                   atol=5e-5)


def _index_operands(case, S, J, Di):
    """Indexer operands [1, J, S, Di], [1, S, Di], [1, S, J] and the blocks
    of 128 rows that hold a tie at a threshold.  Positive queries and keys
    keep relu's exact zeros, which tie, out of the float rows."""
    k = jax.random.split(jax.random.key(7), 3)
    qi = jnp.abs(jax.random.normal(k[0], (1, J, S, Di)))
    ki = jnp.abs(jax.random.normal(k[1], (1, S, Di)))
    w = jax.random.normal(k[2], (1, S, J))
    if case == "all_tied":
        return jnp.round(qi), jnp.round(ki), jnp.round(2 * w), range(S // 128)
    if case == "one_block_tied":
        # whole numbers in the second block's rows and in the keys it sees
        qi = qi.at[:, :, 128:256].set(jnp.round(qi[:, :, 128:256]))
        ki = ki.at[:, :256].set(jnp.round(ki[:, :256]))
        w = w.at[:, 128:256].set(jnp.round(2 * w[:, 128:256]))
        return qi, ki, w, [1]
    if case == "short_rows":
        # two keys with one score in every row, both taken while the row
        # takes all it sees: a threshold shared and no tie to break
        ki = ki.at[:, 1].set(ki[:, 0])
    return qi, ki, w, []


@pytest.mark.parametrize("case,topk", [
    ("no_tie", 64), ("one_block_tied", 64), ("all_tied", 64),
    ("short_rows", 512)])
def test_a_block_runs_the_tie_passes_only_where_it_has_a_tie(case, topk):
    """`index_topk` bisects the cut's position, 9 passes at 512 keys, only
    in a block of rows where some row's threshold is tied ACROSS its
    selection; every other block takes the last key at the threshold in
    one pass.  Either way `aux` is one number: the row's want-th largest
    score and the last position taken at it, as a stable sort of the
    kernels' own scores gives them, and the mask is that sort's."""
    S, J, Di = 512, 3, 16
    qi, ki, w, tied_blocks = _index_operands(case, S, J, Di)
    kit = ki.transpose(0, 2, 1)
    aux = sa.select(qi, kit, w, topk, 128)
    keep = np.asarray(sa.keep_mask(qi, kit, aux, 128, 128))[0].astype(bool)
    scores = np.asarray(sa.index_rows(qi, kit, w, 128))[0]
    aux = np.asarray(aux)[0]
    want = np.zeros((S, S), bool)
    tau, cut = np.zeros(S, np.float32), np.zeros(S, np.float32)
    straddled = np.zeros(S, bool)
    for t in range(S):
        row = scores[t, :t + 1]
        order = np.argsort(-row, kind="stable")[:min(t + 1, topk)]
        want[t, order] = True
        tau[t] = row[order[-1]]
        cut[t] = order[row[order] == tau[t]].max()
        straddled[t] = (row >= tau[t]).sum() > len(order)
    assert (keep == want).all()
    assert (aux[:, :J] == np.asarray(w)[0]).all()
    assert (aux[:, J].view(np.int32) == tau.view(np.int32)).all()
    assert (aux[:, J + 1] == cut).all()
    # the blocks the data were made to tie in, and no other
    assert (np.flatnonzero(straddled.reshape(-1, 128).any(1)).tolist()
            == list(tied_blocks))
    short, long = sa.select_pass_counts(S)
    assert (short, long) == (33, 41)
    passes = np.asarray(sa.select_passes(aux, J))
    assert (passes.reshape(-1, 128) == passes[::128, None]).all()
    assert passes[::128].tolist() == [
        long if block in tied_blocks else short for block in range(S // 128)]
    if case == "short_rows":
        # a row short of topk: its threshold is its least score, and the
        # pair with one score is taken whole
        assert (tau[:topk] == [scores[t, :t + 1].min()
                               for t in range(topk)]).all()
        assert (scores[1:, 0] == scores[1:, 1]).all()
        assert (keep[1:topk, :2]).all()


def test_the_model_reads_the_passes_of_every_layers_selection():
    """`keye.select_passes` walks the layers as `chosen_keys` does and
    gives every row the passes its block of `index_topk` ran: one of
    `select_pass_counts`'s two numbers, the same in a block's 128 rows."""
    family = _family(jnp.float32, tiny_keye.FLOAT32, layers=[0, 1])
    tokens = seeded.batch(family, 0, 1)[0]
    passes = np.asarray(jax.jit(lambda p, t: keye.select_passes(
        p, t, family.cfg, family._streams(t)))(
            seeded.params(family, 0), tokens))
    assert passes.shape == (2, 1, family.seq_len)
    assert set(np.unique(passes)) <= set(sa.select_pass_counts(
        family.seq_len))
    blocks = passes.reshape(2, -1, sa.select_rows(family.seq_len))
    assert (blocks == blocks[..., :1]).all()


def test_rows_short_of_topk_attend_to_everything():
    """A sequence no longer than `topk`: every row takes all it sees, and
    the layer's attention is dense causal attention."""
    B, H, Hkv, S, D, J, Di = 1, 4, 2, 256, 32, 3, 16
    k = jax.random.split(jax.random.key(1), 6)
    q = jax.random.normal(k[0], (B, H, S, D))
    kk, v = (jax.random.normal(k[i], (B, Hkv, S, D)) for i in (1, 2))
    qi = jax.random.normal(k[3], (B, J, S, Di))
    ki = jax.random.normal(k[4], (B, S, Di))
    w = jax.random.normal(k[5], (B, S, J))
    out, kept = sa.selected_attention(q, kk, v, qi, ki, w, S, 128, 128)
    assert (np.asarray(kept)[0] == np.arange(S) + 1).all()
    rep = [jnp.repeat(t, H // Hkv, axis=1) for t in (kk, v)]
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(tfm.dense_attention(q, *rep, causal=True)),
        atol=2e-5)


def test_sectioned_rotary_and_plain_rotary_as_it_was():
    x = jax.random.normal(jax.random.key(0), (2, 3, 64, 16))
    t = jnp.arange(64)
    equal = jnp.stack([t, t, t])
    plain = tfm._rope(x, 10000.0)
    np.testing.assert_allclose(
        np.asarray(tfm._rope(x, 10000.0, positions=equal,
                             sections=(2, 2, 4))), np.asarray(plain),
        atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(tfm._rope(x, 10000.0, positions=t)), np.asarray(plain),
        atol=1e-6)
    grid = jnp.stack([t, t // 8, t % 8])
    turned = np.asarray(tfm._rope(x, 10000.0, positions=grid,
                                  sections=(2, 2, 4)))
    # pairs 0-1 turn by the first stream, as plain rotary; the others not
    both = np.asarray(plain)
    for pair in range(8):
        same = np.allclose(turned[..., [pair, 8 + pair]],
                           both[..., [pair, 8 + pair]], atol=1e-6)
        assert same == (pair < 2), pair
    # against the reference's closed form, a batch of streams
    streams = jnp.broadcast_to(grid[:, None], (3, 2, 64))
    got = tfm._rope(x, 10000.0, positions=streams, sections=(2, 2, 4))
    want = jnp.stack([reference.rotary(x[b], 10000.0, streams[:, b],
                                       (2, 2, 4)) for b in range(2)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    with pytest.raises(ValueError):
        tfm._rope(x, 10000.0, positions=grid, sections=(2, 2, 2))

    # existing callers: the same jaxpr as the function had before it
    # learnt of positions
    def as_it_was(x, theta, inv_freq=None, amplitude=1.0):
        half = x.shape[-1] // 2
        if inv_freq is None:
            freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
        else:
            freqs = jnp.asarray(inv_freq, jnp.float32)
        angles = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] \
            * freqs[None, :]
        cos, sin = jnp.cos(angles), jnp.sin(angles)
        if amplitude != 1.0:
            cos, sin = cos * amplitude, sin * amplitude
        x32 = x.astype(jnp.float32)
        x1, x2 = x32[..., :half], x32[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x2 * cos + x1 * sin], axis=-1).astype(x.dtype)
    xb = x.astype(jnp.bfloat16)
    inv = np.linspace(1.0, 0.01, 8).astype(np.float32)
    for args in ((10000.0,), (0.0, inv, 1.5)):
        assert str(jax.make_jaxpr(lambda x: tfm._rope(x, *args))(xb)) == str(
            jax.make_jaxpr(lambda x: as_it_was(x, *args))(xb))


def test_the_shares_add_up_to_the_layer():
    """Guide, section 4: over the eight chips that share a layer, the
    routed parts the shares compute (there is no shared expert to count
    once) are the uncut reference's expert layer, for the same tokens,
    every pair on exactly one chip; what every chip computes alike, the
    attention over the selected keys, is the uncut reference's; and the
    eight slices' logits laid side by side are the whole head's."""
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

    # the attention half holds no share: the program's on one chip is the
    # reference's own, selection and all
    params = seeded.params(family, 0)
    lp = jax.tree.map(lambda a: a[0], params["moe"])
    x = jax.random.normal(k[0], (1, 256, D))
    mine, kept = keye._attention(x, lp, cfg, afmoe.FULL)
    with jax.default_matmul_precision("highest"):
        ctx, _ = reference.attention_half(
            x, lp, spec, reference.text_positions(jnp.zeros((1, 256))))
    np.testing.assert_allclose(np.asarray(mine - x), np.asarray(ctx),
                               atol=2e-5)
    assert (np.asarray(kept)[0]
            == np.minimum(np.arange(256) + 1, cfg.index_topk)).all()

    V = 8 * 40
    head = jax.random.normal(k[0], (V, D))
    x = jax.random.normal(k[1], (2, 16, D))
    side_by_side = jnp.concatenate(
        [afmoe.head_logits(x, head[c * 40:(c + 1) * 40]) for c in range(8)],
        axis=-1)
    np.testing.assert_allclose(np.asarray(side_by_side),
                               np.asarray(x @ head.T), atol=1e-4, rtol=1e-5)


def test_the_indexer_gets_no_gradient_and_the_choice_is_found_once():
    """The indexer's columns of `in_w` and of `k_norm` get EXACTLY zero
    from the loss, the main heads' do not; under the remat policy
    "selection" a layer's backward pass keeps what the forward pass
    selected and `index_topk` runs once a layer, under "none" twice."""
    family = _family(jnp.float32, layers=[0, 1])
    cfg = family.cfg
    params, batch = seeded.params(family, 0), seeded.batch(family, 0, 1)
    grads = jax.grad(family.loss)(params, batch)["moe"]
    first = (cfg.num_heads + 2 * cfg.num_kv_heads) * cfg.head_dim
    assert not np.asarray(grads["in_w"][..., first:]).any()
    assert not np.asarray(grads["k_norm"][..., cfg.head_dim:]).any()
    assert np.asarray(grads["in_w"][..., :first]).any()
    assert np.asarray(grads["k_norm"][..., :cfg.head_dim]).any()
    assert cfg.remat_policy == "selection"

    def selections(policy):
        changed = dataclasses.replace(cfg, remat_policy=policy)
        text = str(jax.make_jaxpr(jax.grad(
            lambda p: keye.loss_fn(p, batch, changed)))(params))
        return text.count("name=index_topk")
    # a scan over one period of one layer: calls a layer
    assert (selections("selection"), selections("none")) == (1, 2)


def test_parameter_count_at_the_published_widths():
    """The cell's share, counted from the built tree: 465,390,848, of
    which the indexer 2,260,992 a layer; 7.45 GB at 16 bytes each."""
    with open(os.path.join(manifest.BENCH, "configs",
                           "keye-vl-2.0-30b-a3b.json")) as f:
        config = json.load(f)
    family = family_keye.Family(config, config["job"])
    shapes = jax.eval_shape(family.init, jax.random.key(0))
    sizes = {k: int(np.prod(v.shape)) for k, v in shapes["moe"].items()}
    layer = sum(sizes.values()) // 4
    assert sizes["in_w"] // 4 == 2048 * (5120 + 1024 + 64 + 16)
    assert layer == (2048 * 5120 + 4096 * 2048 + 2_260_992 + 262_144
                     + 16 * 3 * 2048 * 768 + 2048 + 2048 + 128 + 192)
    total = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(shapes))
    assert total == 4 * layer + 2 * 18_992 * 2048 + 2048 == 465_390_848
    assert family.cfg.moe.hold_held_weight
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    # no width differs from the source
    for key, value in config["published"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert f"{total:,}" in config["deployment"]["parameters"]


# sha256 of the lowered text of `value_and_grad(family.loss)` at tiny
# widths, the counters in its functions' names taken out
# (`@argsort_286` -> `@argsort`).  gpt2's is the text of the parent commit
# of PR 49 (007eeae) still.  PR 49 laid `checkpoint_name` over the flash
# call's `o` and `lse` and over the expert layer's routing: under these
# cells' policies ("none"; keye's "selection", which lists other names)
# that moved the counters and not one operation.  mellum, afmoe and keye
# were hashed again on PR 52's tree, which CHANGES their expert layers'
# operations (the rows move by `ops/moe_rows.py`'s kernel, the router's
# gathers of single numbers are compares and sums, the plan sorts twice)
# and nothing else of them: a later PR that does not touch
# `parallel/dropless_moe.py` or `ops/moe_rows.py` leaves all four as they
# are (a moved line in either file does not change the text).  keye was
# hashed again on PR 54's tree, which changes the body of ITS kernel
# `index_topk` (`ops/sparse_attention.py` `_select_kernel`: the passes a
# block runs) and nothing else; the other three do not call it and kept
# their texts.
PARENTS_LOWERED_STEPS = {
    "mellum": (tiny_mellum.config, family_mellum,
               "87775117ae84fe979b9bfa072483b80f"
               "8e7d697dc958fa05f34da88de4989594"),
    "afmoe": (tiny_afmoe.config, family_afmoe,
              "57938723e23e75dfff28a5b281552d2b"
              "89df13194d90a54d345705e33daf8f53"),
    "gpt2": (tiny_gpt2_config, family_gpt2,
             "fc640a6643cb19be841478581525d3dd"
             "5a3b42543fb3656c8c3f6d063f8187ca"),
    "keye": (tiny_keye.config, family_keye,
             "dc53e17b9f55173a83df5b93ea72df31"
             "6111c3424b0dcb0858a7bccbc9d391b1"),
}


@pytest.mark.parametrize("name", PARENTS_LOWERED_STEPS)
def test_the_other_decoders_steps_lower_to_the_text_they_had(name):
    make_config, module, digest = PARENTS_LOWERED_STEPS[name]
    config = make_config()
    family = module.Family(config, config["job"])
    params = jax.eval_shape(family.init, jax.random.key(0))
    batch = jax.eval_shape(lambda k: family.make_batch(k, 2),
                           jax.random.key(0))
    text = jax.jit(jax.value_and_grad(family.loss)).lower(
        params, batch).as_text()
    text = re.sub(r"(@[A-Za-z_][\w.]*?)_\d+\b", r"\1", text)
    assert hashlib.sha256(text.encode()).hexdigest() == digest


def test_the_attention_adapter_is_one_table():
    assert set(afmoe._ATTENTION) == {afmoe.SLIDING, afmoe.FULL,
                                     afmoe.SELECTED, afmoe.BLOCK_DIFFUSION}
    with pytest.raises(KeyError):
        afmoe._attn_fn(None, "no_such_attention")
    with pytest.raises(ValueError):
        sa.check_blocks(384, 128, 256)
    assert sa.auto_blocks(32768) == (128, 512) and sa.auto_blocks(200) == (
        0, 0)
    assert sa.selected_pairs(32768, 2048) == 2048 * 2049 // 2 + 30720 * 2048
