"""The ouro model (`byteps_tpu/models/ouro.py`): the parameter count at
the published widths, the loop against the stack it stands for (a model
of L layers walked T times is a T x L-layer model built from T copies of
the same weights, and a shared leaf's gradient the SUM of the copies'),
the exit distribution, the streamed head over the walks' rows against the
plain one, the gate's gradient, the counters and what the configuration
refuses; the queries' and keys' rotary turn on `ops/head_norm_rope.py`'s
kernels against the jnp form, and the parent's program where the rule of
shapes says no.  The program against its plain float32 reference
(`benchmark/reference/ouro.py`), loss and every gradient leaf:
`test_ouro_reference.py` and the unbroken case of
`test_ouro_variants.py`."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import ouro as family_ouro
from benchmark.harness import manifest
from benchmark.tests import ouro_variants, tiny_ouro
from byteps_tpu.models import afmoe, ouro
from byteps_tpu.models.transformer import _rms_norm, _rope
from family_cases import Cases
from testutil import qk_kernels_train_as_the_jnp_form_did

CASES = Cases(tiny_ouro, family_ouro.Family)
CELL = "ouro-2.6b.ingraph-1chip"


@pytest.fixture(scope="module")
def small():
    """Two layers walked three times, float32, one sequence of 128."""
    family = CASES.family(jnp.float32, layers=[0, 1], walks=3)
    return family, *CASES.operands(family)


def test_parameter_count_at_the_published_widths():
    """Counted from the tree the cell's family builds (shapes alone): a
    layer 16,777,216 (q, k, v, o) + 34,603,008 (SwiGLU) + 8,192 (four norm
    scales) = 51,388,416; eight of them, embedding and untied head of
    49,152 rows, the final norm, the gate's 2,048 + 1 in one leaf."""
    cell = manifest.load_cell(CELL)
    family = family_ouro.Family(cell.config, cell.job)
    tree = jax.eval_shape(family.init, jax.random.key(0))
    layer = sum(int(np.prod(leaf.shape[1:]))
                for leaf in jax.tree.leaves(tree["dense"]))
    assert layer == 16_777_216 + 34_603_008 + 8_192 == 51_388_416
    total = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))
    assert total == 8 * layer + 2 * 100_663_296 + 2_048 + 2_049
    assert total == 612_438_017 == ouro.num_params(family.cfg)
    assert total == cell.config["deployment"]["parameter_count"]
    assert all(leaf.dtype == jnp.float32 for leaf in jax.tree.leaves(tree))
    cfg = family.cfg
    assert (cfg.num_layers, cfg.total_ut_steps, cfg.num_heads,
            cfg.num_kv_heads, cfg.head_dim, cfg.vocab_size,
            family.seq_len) == (8, 4, 16, 16, 128, 49_152, 8_192)
    # a depth the issue's rule would fall back to
    assert ouro.num_params(dataclasses.replace(cfg, num_layers=7)) == (
        561_049_601)
    # 6 a matmul parameter a token meets in each of the four walks, the
    # head's among them, and the causal pairs of 32 layer applications
    per_token = family.model_flops_per_sample() / family.seq_len
    assert per_token == (6.0 * 4 * (8 * 51_380_224 + 100_663_296 + 2_048)
                         + 12.0 * 32 * 8_193 / 2 * 2_048)
    assert 15.4e9 < per_token < 15.6e9


def _stack_loss(shared, copies, batch, cfg):
    """The loss of the T x L-layer model: `copies` are T trees of the
    layers' weights, one a walk, with the norm and the gate between."""
    tokens, targets = batch
    x = ouro._embed(shared, tokens, cfg)
    h, lam = [], []
    for group in copies:
        x, _ = afmoe.run_layers({"dense": group}, x, cfg, layer=ouro._layer)
        x = _rms_norm(x, shared["final_ln"], None, eps=cfg.rms_norm_eps)
        h.append(x)
        lam.append(ouro.exit_gate(shared, x))
    p = ouro.exit_distribution(jnp.stack(lam))
    task = ouro.weighted_nll_sum(shared, jnp.stack(h), targets, p, cfg)
    return (task / targets.size
            - cfg.exit_entropy_beta * ouro.exit_entropy(p).mean())


def test_the_loop_is_the_stack(small):
    family, params, batch = small
    cfg = family.cfg
    T = cfg.total_ut_steps
    shared = {k: v for k, v in params.items() if k != "dense"}
    loss, grads = jax.jit(jax.value_and_grad(family.loss))(params, batch)
    stack, (g_shared, g_copies) = jax.jit(jax.value_and_grad(
        lambda s, c: _stack_loss(s, c, batch, cfg), argnums=(0, 1)))(
        shared, [params["dense"]] * T)
    np.testing.assert_allclose(float(loss), float(stack), rtol=1e-6)
    assert len(g_copies) == T == 3
    for name, got in grads["dense"].items():
        copies = [np.asarray(g[name]) for g in g_copies]
        want = sum(copies)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-5 * np.abs(want).max())
        # and no walk's part is nothing: a leaf's gradient is not one
        # copy's
        assert all(np.abs(c).max() > 0 for c in copies)
        assert np.abs(np.asarray(got) - copies[-1]).max() > 0
    for name, got in g_shared.items():
        want = np.asarray(grads[name])
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-5 * np.abs(want).max())


def test_the_exit_distribution():
    lam = jax.random.uniform(jax.random.key(3), (4, 2, 8), jnp.float32)
    p = np.asarray(ouro.exit_distribution(lam))
    lam = np.asarray(lam)
    np.testing.assert_allclose(p.sum(0), 1.0, rtol=1e-6)
    np.testing.assert_allclose(p[0], lam[0])
    np.testing.assert_allclose(p[2], lam[2] * (1 - lam[0]) * (1 - lam[1]),
                               rtol=1e-6)
    np.testing.assert_allclose(
        p[3], (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2]), rtol=1e-6)
    # the last gate is unused
    other = np.asarray(ouro.exit_distribution(
        jnp.asarray(lam).at[3].set(0.123)))
    np.testing.assert_array_equal(other, p)
    half = ouro.exit_distribution(jnp.full((4, 1, 1), 0.5))
    counters = ouro.exit_counters(half, jnp.ones((4, 1, 1)))
    assert float(counters["expected_steps"]) == pytest.approx(1.875)
    assert float(counters["entropy"]) == pytest.approx(1.75 * np.log(2.0))
    assert float(ouro.exit_entropy(jnp.full((4, 1), 0.25))[0]) == (
        pytest.approx(np.log(4.0)))
    # a saturated gate: 0 log 0 = 0, and a finite gradient
    sure = jnp.asarray([1.0, 0.5, 0.5, 0.5]).reshape(4, 1)
    value, grad = jax.jit(jax.value_and_grad(
        lambda lam: ouro.exit_entropy(ouro.exit_distribution(lam)).sum()))(
        sure)
    assert float(value) == 0.0 and np.isfinite(np.asarray(grad)).all()
    # one walk leaves at it
    assert np.asarray(ouro.exit_distribution(jnp.full((1, 3), 0.3))).tolist(
        ) == [[1.0, 1.0, 1.0]]


def test_the_streamed_head_over_the_walks_rows_is_the_plain_one(small):
    """One call of the streamed head over T x S rows under `p` against
    the unstreamed logits; a row's NLL is the gradient with respect to
    its weight; and `weights_held_constant` zeroes the task term's
    gradient on the gate."""
    family, params, batch = small
    cfg = family.cfg
    tokens, targets = batch
    h, lam = jax.jit(lambda q, t: ouro.walks(q, t, cfg))(params, tokens)
    assert h.shape == (3, *tokens.shape, cfg.hidden_size)
    assert lam.shape == (3, *tokens.shape) and lam.dtype == jnp.float32
    p = ouro.exit_distribution(lam)
    assert cfg.ce_chunk_rows == 128     # three chunks, one a walk
    plain = dataclasses.replace(cfg, ce_chunk_rows=0)
    odd = dataclasses.replace(cfg, ce_chunk_rows=96)    # chunks across walks

    @jax.jit
    def sums(h, p):
        return [ouro.weighted_nll_sum(params, h, targets, p, c)
                for c in (plain, cfg, odd)]
    want, *streamed = map(float, sums(h, p))
    np.testing.assert_allclose(streamed, want, rtol=1e-6)
    rows = jax.jit(lambda h: ouro.nll_rows(params, h, targets, cfg))(h)
    logp = jax.nn.log_softmax(afmoe.head_logits(h, params["head"]), -1)
    np.testing.assert_allclose(
        np.asarray(rows), -np.asarray(jnp.take_along_axis(
            logp, jnp.broadcast_to(targets, lam.shape)[..., None], -1))[..., 0],
        rtol=1e-5)
    np.testing.assert_allclose(float((rows * p).sum()), want, rtol=1e-6)
    np.testing.assert_allclose(
        float(jax.jit(family.loss)(params, batch)),
        want / targets.size - cfg.exit_entropy_beta * float(
            ouro.exit_entropy(p).mean()), rtol=1e-6)

    # the gate's gradient: the task term's part is there, and is gone
    # where `p` is held constant in it
    def gate_grad(beta):
        at = dataclasses.replace(cfg, exit_entropy_beta=beta)
        grads = jax.jit(jax.grad(lambda q: ouro.loss_fn(q, batch, at)))(
            params)
        return np.asarray(grads["exit_gate"])
    assert np.abs(gate_grad(0.0)).max() > 0
    with ouro_variants.weights_held_constant(family):
        # through the layers the gate's weight reaches nothing else
        assert not gate_grad(0.0).any()
        assert np.abs(gate_grad(0.05)).max() > 0


def test_the_counters(small):
    import byteps_tpu as bps
    family, params, batch = small
    cfg = family.cfg
    assert ouro.loop_counters(cfg, 2, 128) == {
        "steps": 3, "layer_applications": 6,
        "kept_bytes": 6 * 2 * 128 * cfg.hidden_size * 4}
    cell = manifest.load_cell(CELL)
    big = family_ouro.Family(cell.config, cell.job).cfg
    assert ouro.loop_counters(big, 1, 8192) == {
        "steps": 4, "layer_applications": 32,
        "kept_bytes": 32 * 8192 * 2048 * 2}
    jax.eval_shape(family.loss, params, batch)      # tracing sets them
    metrics = bps.get_metrics()
    assert metrics["bps_loop_steps"] == 3
    assert metrics["bps_loop_layer_applications"] == 6
    assert metrics["bps_loop_kept_bytes"] == 6 * batch[0].size * 64 * 4
    h, lam = ouro.walks(params, batch[0], cfg)
    p = ouro.exit_distribution(lam)
    counters = ouro.exit_counters(p, ouro.nll_rows(params, h, batch[1], cfg))
    assert float(counters["share"].sum()) == pytest.approx(1.0, rel=1e-5)
    assert 1.0 < float(counters["expected_steps"]) < 3.0
    assert 0.0 < float(counters["entropy"]) <= np.log(3.0)
    # a random model predicts nothing: every walk's NLL is near ln(512)
    assert all(5.5 < float(x) < 7.5 for x in counters["nll"])
    ouro.record_exit(counters)
    metrics = bps.get_metrics()
    assert metrics["bps_exit_expected_steps"] == pytest.approx(
        float(counters["expected_steps"]))
    assert metrics["bps_exit_entropy"] == pytest.approx(
        float(counters["entropy"]))
    for t in range(3):
        assert metrics[f'bps_exit_share{{step="{t + 1}"}}'] == pytest.approx(
            float(counters["share"][t]))
        assert metrics[f'bps_loop_nll{{step="{t + 1}"}}'] == pytest.approx(
            float(counters["nll"][t]))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_turn_kernels_train_the_model_the_jnp_form_did(monkeypatch,
                                                           dtype):
    """A layer walked twice at heads of 128 over 128 rows, its queries and
    keys turned by `ops/head_norm_rope.py`'s kernels (no scale: the turn
    alone), against the same program on the jnp form: float32 to rounding;
    in bfloat16 no further from the float32 program than the jnp form."""
    qk_kernels_train_as_the_jnp_form_did(
        lambda dtype: CASES.family(dtype, layers=[0], walks=2), monkeypatch,
        dtype)


def _parents_queries_and_keys(t, q_scale, k_scale, cos, sin, *, eps, heads,
                              kv_heads, theta):
    """`models/ouro.py` `_attention`'s lines for q and k until PR 65: the
    projection's result split, laid out by head, turned by
    `transformer._rope`, which makes its tables itself from `theta`."""
    assert q_scale is None and k_scale is None
    B, S, _ = t.shape
    Dh = 2 * cos.shape[-1]
    q, k, _ = jnp.split(t, [heads * Dh, (heads + kv_heads) * Dh], axis=-1)

    def by_head(x):
        return x.reshape(B, S, -1, Dh).transpose(0, 2, 1, 3)
    return _rope(by_head(q), theta), _rope(by_head(k), theta)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("head_dim,seq_len", [(64, 128), (128, 96)],
                         ids=["a_head_of_64", "a_ragged_sequence"])
def test_where_the_rule_says_no_the_program_is_the_parents(
        monkeypatch, head_dim, seq_len, dtype):
    """A head of half a lane tile, or a sequence of no whole tiles of
    rows: no kernel is called, and loss and every gradient are, bit for
    bit, those of the program whose queries and keys go through
    `transformer._rope` as they did."""
    import byteps_tpu as bps
    from byteps_tpu.common import telemetry
    from byteps_tpu.ops import head_norm_rope
    family = CASES.family(dtype, layers=[0], walks=2)
    # (the flash adapter wants whole tiles of rows too: dense attention)
    cfg = family.cfg = dataclasses.replace(
        family.cfg, head_dim=head_dim,
        attn_impl="flash" if seq_len % 128 == 0 else "dense")
    params = family.init(jax.random.key(0))
    batch = ouro.synthetic_batch(jax.random.key(1), 1, seq_len, cfg)
    telemetry.record_static("head_norm_rope", kernel=0)
    got = jax.jit(jax.value_and_grad(family.loss))(params, batch)
    assert bps.get_metrics()["bps_head_norm_rope_kernel"] == 0
    monkeypatch.setattr(head_norm_rope, "queries_and_keys", functools.partial(
        _parents_queries_and_keys, theta=cfg.rope_theta))
    want = jax.jit(jax.value_and_grad(family.loss))(params, batch)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_a_batch_and_what_the_configuration_refuses():
    cfg = CASES.family(jnp.float32, layers=[0]).cfg
    tokens, targets = ouro.synthetic_batch(jax.random.key(5), 3, 64, cfg)
    assert tokens.shape == targets.shape == (3, 64)
    np.testing.assert_array_equal(np.asarray(tokens[:, 1:]),
                                  np.asarray(targets[:, :-1]))
    assert 0 <= int(tokens.min()) and int(tokens.max()) < cfg.vocab_size
    assert cfg.layer_types == (afmoe.FULL,) and cfg.num_dense_layers == 1
    assert afmoe._stack_plan(dataclasses.replace(cfg, num_layers=8)) == [
        ("dense", (afmoe.FULL,), 8)]
    for bad in (dict(num_kv_heads=3), dict(head_dim=15),
                dict(attn_impl="ring"), dict(num_layers=0),
                dict(total_ut_steps=0)):
        with pytest.raises(ValueError):
            dataclasses.replace(cfg, **bad)
    with pytest.raises(ValueError, match="positions"):
        config = tiny_ouro.config()
        family_ouro.Family(config, {**config["job"], "seq_len": 1 << 17})
    with pytest.raises(ValueError, match="held"):
        config = tiny_ouro.config()
        config["held"]["num_hidden_layers"] = 7
        family_ouro.Family(config, config["job"])
