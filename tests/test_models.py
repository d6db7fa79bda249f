"""Model-family tests: shapes, loss decrease, DP training integration.

Mirrors the reference strategy of integration-level tests that train a
small real model a few steps (reference: tests/test_onebit.py trains a
gluoncv model; tests/test_tensorflow_keras.py trains a small keras model).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import byteps_tpu as bps
from byteps_tpu import models
from byteps_tpu.models import transformer as tfm


def test_transformer_forward_shapes():
    cfg = tfm.get_config("tiny")
    params = tfm.init_params(jax.random.key(0), cfg)
    toks = jnp.zeros((2, 16), jnp.int32)
    logits = tfm.forward(params, toks, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert jnp.isfinite(logits).all()


def test_transformer_causality():
    """Changing a future token must not affect earlier logits (causal)."""
    cfg = tfm.get_config("tiny", remat=False, dtype=jnp.float32)
    params = tfm.init_params(jax.random.key(0), cfg)
    t1 = jnp.zeros((1, 8), jnp.int32)
    t2 = t1.at[0, 7].set(5)
    l1 = tfm.forward(params, t1, cfg)
    l2 = tfm.forward(params, t2, cfg)
    np.testing.assert_allclose(l1[0, :7], l2[0, :7], rtol=1e-5, atol=1e-5)
    assert not np.allclose(l1[0, 7], l2[0, 7])


def test_llama_block_forward_and_causality():
    """Llama-class config (RMSNorm + SwiGLU + RoPE + GQA, no biases):
    shapes, finiteness, causal masking, and the conditional param tree."""
    cfg = tfm.get_config("llama_tiny", remat=False, dtype=jnp.float32)
    params = tfm.init_params(jax.random.key(0), cfg)
    lp = params["layers"]
    assert "mlp_gate_w" in lp and "qkv_b" not in lp and "ln1_bias" not in lp
    assert "pos_embed" not in params
    qkv_cols = (cfg.num_heads + 2 * cfg.kv_heads) * cfg.head_dim
    assert lp["qkv_w"].shape == (cfg.num_layers, cfg.d_model, qkv_cols)

    t1 = jnp.zeros((1, 8), jnp.int32)
    t2 = t1.at[0, 7].set(5)
    l1 = tfm.forward(params, t1, cfg)
    assert l1.shape == (1, 8, cfg.vocab_size) and np.isfinite(l1).all()
    l2 = tfm.forward(params, t2, cfg)
    np.testing.assert_allclose(l1[0, :7], l2[0, :7], rtol=1e-5, atol=1e-5)
    assert not np.allclose(l1[0, 7], l2[0, 7])


def test_llama_config_validation():
    with pytest.raises(ValueError):   # non-integer GQA group
        tfm.get_config("llama_tiny", num_heads=6, num_kv_heads=4,
                       d_model=96)
    with pytest.raises(ValueError):   # 0 must not silently mean MHA
        tfm.get_config("llama_tiny", num_kv_heads=0)
    with pytest.raises(ValueError):   # rope needs even head_dim
        tfm.get_config("llama_tiny", d_model=60, num_heads=4,
                       num_kv_heads=2)
    with pytest.raises(ValueError):   # d_model % num_heads
        tfm.get_config("tiny", d_model=65)
    for field in ("norm", "act", "pos"):  # enum typos must not silently
        with pytest.raises(ValueError):   # drop positions/gating
            tfm.get_config("llama_tiny", **{field: "bogus"})


def test_llama_rope_rotation_properties():
    """RoPE is a pure rotation: position 0 is the identity, norms are
    preserved at every position, and distinct positions rotate the same
    vector differently."""
    x = jax.random.normal(jax.random.key(3), (1, 2, 6, 8), jnp.float32)
    y = tfm._rope(x, theta=10000.0)
    np.testing.assert_allclose(y[:, :, 0], x[:, :, 0], rtol=1e-6)  # pos 0
    np.testing.assert_allclose(np.linalg.norm(y, axis=-1),
                               np.linalg.norm(x, axis=-1), rtol=1e-5)
    same_vec = jnp.broadcast_to(x[:, :, :1], x.shape)
    r = tfm._rope(same_vec, theta=10000.0)
    assert not np.allclose(r[0, 0, 1], r[0, 0, 4], atol=1e-5)
    # relative-position property: q.k dot depends only on distance
    q = tfm._rope(same_vec, 10000.0)
    dots = jnp.einsum("bhsd,bhtd->bhst", q, q)[0, 0]
    np.testing.assert_allclose(np.diag(dots, k=1)[0], np.diag(dots, k=1)[3],
                               rtol=1e-5)


def test_llama_gqa_matches_mha_when_kv_heads_equal():
    """num_kv_heads == num_heads degenerates to standard MHA bit-for-tol
    (same param tree shapes, repeat() becomes identity)."""
    base = tfm.get_config("llama_tiny", remat=False, dtype=jnp.float32)
    cfg_g = tfm.get_config("llama_tiny", remat=False, dtype=jnp.float32,
                           num_kv_heads=base.num_heads)
    params = tfm.init_params(jax.random.key(4), cfg_g)
    toks = jax.random.randint(jax.random.key(5), (2, 12), 0, base.vocab_size)
    l_explicit = tfm.forward(params, toks, cfg_g)
    cfg_none = tfm.get_config("llama_tiny", remat=False, dtype=jnp.float32,
                              num_kv_heads=None)
    l_none = tfm.forward(params, toks, cfg_none)
    np.testing.assert_allclose(l_explicit, l_none, rtol=1e-6, atol=1e-6)


def test_llama_training_loss_decreases(mesh8):
    cfg = tfm.get_config("llama_tiny")
    params = tfm.init_params(jax.random.key(0), cfg)
    toks, tgts = tfm.synthetic_batch(jax.random.key(1), 16, 32, cfg)
    opt = bps.DistributedOptimizer(optax.adam(1e-3))
    step = bps.build_train_step(lambda p, b: tfm.loss_fn(p, b, cfg), opt,
                                mesh8)
    s = opt.init(params)
    losses = []
    for _ in range(6):
        params, s, loss = step(params, s, (toks, tgts))
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_llama_param_specs_tree_matches_params():
    cfg = tfm.get_config("llama_tiny")
    params = tfm.init_params(jax.random.key(0), cfg)
    specs = tfm.param_specs(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))


def test_every_named_config_is_consistent():
    """Every CONFIGS entry builds, and its param tree (via eval_shape —
    bench-scale configs never materialize) matches its TP spec tree leaf
    for leaf, with spec ranks == param ranks."""
    for name in tfm.CONFIGS:
        cfg = tfm.get_config(name)
        shapes = jax.eval_shape(lambda k, c=cfg: tfm.init_params(k, c),
                                jax.random.key(0))
        specs = tfm.param_specs(cfg)
        is_spec = lambda x: isinstance(x, jax.sharding.PartitionSpec)
        assert jax.tree.structure(shapes) == jax.tree.structure(
            specs, is_leaf=is_spec), name
        for path, spec in jax.tree.flatten_with_path(
                specs, is_leaf=is_spec)[0]:
            leaf = shapes
            for p in path:
                leaf = leaf[p.key if hasattr(p, "key") else p.idx]
            assert len(spec) <= leaf.ndim, (name, path, spec, leaf.shape)


def test_transformer_remat_matches_no_remat():
    cfg_r = tfm.get_config("tiny", remat=True, dtype=jnp.float32)
    cfg_n = tfm.get_config("tiny", remat=False, dtype=jnp.float32)
    params = tfm.init_params(jax.random.key(1), cfg_r)
    toks = jax.random.randint(jax.random.key(2), (2, 12), 0, cfg_r.vocab_size)
    g_r = jax.grad(tfm.loss_fn)(params, (toks, toks), cfg_r)
    g_n = jax.grad(tfm.loss_fn)(params, (toks, toks), cfg_n)
    for a, b in zip(jax.tree.leaves(g_r), jax.tree.leaves(g_n)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_transformer_remat_policies_match():
    # Selective checkpoint policies change what backward recomputes, never
    # the values; gradients must match the no-remat baseline bit-for-tol.
    cfg_n = tfm.get_config("tiny", remat=False, dtype=jnp.float32)
    params = tfm.init_params(jax.random.key(1), cfg_n)
    toks = jax.random.randint(jax.random.key(2), (2, 12), 0, cfg_n.vocab_size)
    g_n = jax.grad(tfm.loss_fn)(params, (toks, toks), cfg_n)
    for pol in ("dots", "dots_no_batch", "proj"):
        cfg_p = tfm.get_config("tiny", remat=True, remat_policy=pol,
                               dtype=jnp.float32)
        g_p = jax.grad(tfm.loss_fn)(params, (toks, toks), cfg_p)
        for a, b in zip(jax.tree.leaves(g_p), jax.tree.leaves(g_n)):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError):
        tfm.forward(params, toks,
                    tfm.get_config("tiny", remat_policy="bogus"))


def test_scan_unroll_matches_rolled():
    """scan_unroll groups layers per scan iteration — a scheduling knob
    that must never change loss or gradients; invalid factors fail at
    config construction."""
    cfg1 = tfm.get_config("tiny", dtype=jnp.float32)   # tiny has 2 layers
    params = tfm.init_params(jax.random.key(1), cfg1)
    toks = jax.random.randint(jax.random.key(2), (2, 12), 0, cfg1.vocab_size)
    l1, g1 = jax.value_and_grad(tfm.loss_fn)(params, (toks, toks), cfg1)
    cfg2 = tfm.get_config("tiny", dtype=jnp.float32, scan_unroll=2)
    l2, g2 = jax.value_and_grad(tfm.loss_fn)(params, (toks, toks), cfg2)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError):
        tfm.get_config("tiny", scan_unroll=3)  # doesn't divide num_layers
    with pytest.raises(ValueError):
        tfm.get_config("tiny", scan_unroll=0)


def test_fused_ce_matches_dense_loss_and_grads():
    """Streamed LM-head cross-entropy (ce_chunk_rows > 0) must equal the
    full-logits path up to f32 reduction order — loss AND grads, including
    a chunk size that does not divide B*S (padding leg)."""
    cfg_d = tfm.get_config("tiny", remat=False, dtype=jnp.float32)
    params = tfm.init_params(jax.random.key(7), cfg_d)
    toks, tgts = tfm.synthetic_batch(jax.random.key(8), 3, 20, cfg_d)
    l_d, g_d = jax.value_and_grad(tfm.loss_fn)(params, (toks, tgts), cfg_d)
    for chunk in (16, 7, 4096):   # divides/doesn't/one-chunk (> N)
        cfg_f = tfm.get_config("tiny", remat=False, dtype=jnp.float32,
                               ce_chunk_rows=chunk)
        l_f, g_f = jax.value_and_grad(tfm.loss_fn)(params, (toks, tgts),
                                                   cfg_f)
        np.testing.assert_allclose(float(l_f), float(l_d), rtol=1e-5)
        for a, b in zip(jax.tree.leaves(g_f), jax.tree.leaves(g_d)):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-5)


def test_fused_ce_lowering_never_materializes_full_logits():
    """Structural guard at bench geometry (1 layer): the fused path's
    lowered HLO must contain chunk-sized logits buffers only — the full
    [B*S, vocab] f32 tensor (3.2 GB at bench scale) must not appear in
    forward OR backward."""
    cfg = tfm.get_config("bert_large", num_layers=1, causal=True,
                         vocab_size=32768, max_seq_len=512,
                         ce_chunk_rows=2048)
    params = tfm.init_params(jax.random.key(0), cfg)
    toks = jnp.zeros((8, 512), jnp.int32)   # N = 4096 rows

    txt = jax.jit(jax.value_and_grad(
        lambda p: tfm.loss_fn(p, (toks, toks), cfg))).lower(params).as_text()
    assert "tensor<2048x32768xf32>" in txt       # per-chunk logits
    assert "tensor<4096x32768xf32>" not in txt   # flattened full logits
    assert "tensor<8x512x32768xf32>" not in txt  # unflattened full logits
    assert "tensor<2x2048x32768xf32>" not in txt  # stacked chunk residuals


def test_fused_ce_trains(mesh8):
    """End-to-end: the fused-CE config trains under the DP train step."""
    cfg = tfm.get_config("tiny", ce_chunk_rows=64)
    params = tfm.init_params(jax.random.key(0), cfg)
    opt = bps.DistributedOptimizer(optax.adam(1e-3))
    step = bps.build_train_step(lambda p, b: tfm.loss_fn(p, b, cfg), opt,
                                mesh8)
    s = opt.init(params)
    toks, tgts = tfm.synthetic_batch(jax.random.key(3), 16, 32, cfg)
    losses = []
    for _ in range(6):
        params, s, loss = step(params, s, (toks, tgts))
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_transformer_dp_training_loss_decreases(mesh8):
    # remat_policy="proj" here doubles as the named-checkpoint policy's
    # mesh/shard_map composition coverage (single-device parity is pinned
    # by test_transformer_remat_policies_match).
    cfg = tfm.get_config("tiny", dtype=jnp.float32, remat_policy="proj")
    params = tfm.init_params(jax.random.key(0), cfg)
    opt = bps.DistributedOptimizer(optax.adam(1e-3))
    step = bps.build_train_step(
        lambda p, b: tfm.loss_fn(p, b, cfg), opt, mesh8)
    opt_state = opt.init(params)
    toks, tgts = tfm.synthetic_batch(jax.random.key(3), 16, 32, cfg)
    first = None
    for _ in range(10):
        params, opt_state, loss = step(params, opt_state, (toks, tgts))
        if first is None:
            first = float(loss)
    assert float(loss) < first


@pytest.mark.parametrize("name,num_classes", [("resnet18", 10), ("vgg16", 10)])
def test_cnn_forward(name, num_classes):
    model = models.create_cnn(name, num_classes=num_classes)
    x = jnp.ones((2, 32, 32, 3))
    variables = model.init(jax.random.key(0), x, train=False)
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (2, num_classes)
    assert jnp.isfinite(logits).all()


@pytest.mark.slow
def test_resnet_dp_training_step(mesh8):
    model = models.create_cnn("resnet18", num_classes=10)
    x = jnp.ones((8, 32, 32, 3))
    variables = model.init(jax.random.key(0), x, train=False)
    loss = models.cnn_loss_fn(model)
    opt = bps.DistributedOptimizer(optax.sgd(0.1))
    step = bps.build_train_step(loss, opt, mesh8)
    opt_state = opt.init(variables)
    labels = jnp.zeros((8,), jnp.int32)
    v2, opt_state, l0 = step(variables, opt_state, (x, labels))
    assert jnp.isfinite(l0)


def test_mlp_training_loss_decreases(mesh8):
    params = models.init_mlp(jax.random.key(0), (16, 32, 4))
    opt = bps.DistributedOptimizer(optax.sgd(0.5))
    step = bps.build_train_step(models.mlp_loss, opt, mesh8)
    opt_state = opt.init(params)
    x = jax.random.normal(jax.random.key(1), (32, 16))
    y = (x.sum(-1) > 0).astype(jnp.int32)
    losses = []
    for _ in range(20):
        params, opt_state, loss = step(params, opt_state, (x, y))
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_param_specs_tree_matches_params():
    cfg = tfm.get_config("tiny")
    params = tfm.init_params(jax.random.key(0), cfg)
    specs = tfm.param_specs(cfg)
    # same tree structure
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
