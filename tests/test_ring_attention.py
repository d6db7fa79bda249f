"""Ring / Ulysses attention vs dense reference on the 8-device CPU mesh."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from byteps_tpu.models.transformer import dense_attention
from byteps_tpu.ops import ring_attention as ra


def _mesh_sp(n=8):
    return Mesh(np.array(jax.devices()[:n]), ("sp",))


def _qkv(rng, B=2, H=4, S=32, D=8, dtype=jnp.float32):
    ks = jax.random.split(rng, 3)
    return tuple(jax.random.normal(k, (B, H, S, D), dtype) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_dense(causal):
    mesh = _mesh_sp()
    q, k, v = _qkv(jax.random.key(0))
    expect = dense_attention(q, k, v, causal)
    spec = P(None, None, "sp", None)
    f = functools.partial(ra.ring_attention_shard, causal=causal)
    out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(spec,) * 3,
                                out_specs=spec, check_vma=False))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_dense(causal):
    mesh = _mesh_sp()
    q, k, v = _qkv(jax.random.key(1), H=8)
    expect = dense_attention(q, k, v, causal)
    spec = P(None, None, "sp", None)
    f = functools.partial(ra.ulysses_attention_shard, causal=causal)
    out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(spec,) * 3,
                                out_specs=spec, check_vma=False))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_ring_attn_fn_in_transformer():
    """Full transformer forward with ring attention == dense forward."""
    from byteps_tpu.models import transformer as tfm
    mesh = _mesh_sp()
    cfg = tfm.get_config("tiny", remat=False, dtype=jnp.float32)
    params = tfm.init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 32), 0, cfg.vocab_size)
    dense_logits = tfm.forward(params, toks, cfg)
    ring_fn = ra.make_ring_attn_fn(mesh, "sp")
    ring_logits = jax.jit(
        lambda p, t: tfm.forward(p, t, cfg, attn_fn=ring_fn))(params, toks)
    np.testing.assert_allclose(np.asarray(ring_logits),
                               np.asarray(dense_logits), rtol=2e-4, atol=2e-4)


def test_ring_attention_grads_flow():
    """Gradients propagate through the ring (scan + ppermute)."""
    mesh = _mesh_sp()
    q, k, v = _qkv(jax.random.key(2), S=16)
    spec = P(None, None, "sp", None)

    def loss(q, k, v):
        f = functools.partial(ra.ring_attention_shard, causal=True)
        out = jax.shard_map(f, mesh=mesh, in_specs=(spec,) * 3,
                            out_specs=spec, check_vma=False)(q, k, v)
        return (out ** 2).sum()

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    def dense_loss(q, k, v):
        return (dense_attention(q, k, v, True) ** 2).sum()
    ge = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, ge):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_ulysses_rejects_bad_head_count():
    mesh = _mesh_sp()
    q, k, v = _qkv(jax.random.key(3), H=4)  # 4 heads, 8-way sp
    spec = P(None, None, "sp", None)
    with pytest.raises(ValueError, match="divisible"):
        f = functools.partial(ra.ulysses_attention_shard, causal=False)
        jax.shard_map(f, mesh=mesh, in_specs=(spec,) * 3,
                      out_specs=spec, check_vma=False)(q, k, v)


def test_ulysses_with_llama_gqa_block():
    """Composition: a llama-class model (GQA + RoPE + SwiGLU) forwards
    through Ulysses sequence parallelism.  GQA expands kv heads to the
    full head count before the attn_fn runs, so the sp head-split sees a
    uniform head axis; logits must match the plain dense run."""
    from byteps_tpu.models import transformer as tfm
    from byteps_tpu.ops.ring_attention import make_ulysses_attn_fn

    mesh = _mesh_sp()
    cfg = tfm.get_config("llama_tiny", remat=False, dtype=jnp.float32,
                         num_heads=8, num_kv_heads=2, d_model=64)
    params = tfm.init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 32), 0, cfg.vocab_size)
    want = tfm.forward(params, toks, cfg)
    got = tfm.forward(params, toks, cfg,
                      attn_fn=make_ulysses_attn_fn(mesh))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=1e-4)


def test_ulysses_with_flash_inner():
    """Ulysses + flash over a REAL 8-way sp mesh: the all-to-all reshards
    seq->heads (each shard holds 1 head x full sequence), the Pallas
    kernel runs the gathered-sequence attention, and the result matches
    unsharded dense attention."""
    from byteps_tpu.models.transformer import dense_attention
    from byteps_tpu.ops.ring_attention import make_ulysses_attn_fn

    mesh = _mesh_sp()
    rng = np.random.RandomState(11)
    B, H, S, D = 2, 8, 256, 32
    q, k, v = (jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
               for _ in range(3))
    for causal in (False, True):
        want = dense_attention(q, k, v, causal)
        flash_fn = make_ulysses_attn_fn(mesh, attn="flash")
        got = flash_fn(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=1e-4)
    with pytest.raises(ValueError, match="dense"):
        make_ulysses_attn_fn(mesh, attn="nope")
    # Explicit flash must refuse shapes it cannot tile rather than
    # silently materializing the gathered S x S logits as dense.
    strict_fn = make_ulysses_attn_fn(mesh, attn="flash")
    bad = jnp.zeros((1, 8, 8 * 100, 32), jnp.float32)  # S/n=100 -> S=800?
    with pytest.raises(ValueError, match="divisible by 128"):
        strict_fn(bad, bad, bad, False)
