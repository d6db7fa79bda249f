"""The mask of the attention over selected keys as BITS
(`byteps_tpu/ops/sparse_attention.py`): the forward kernel `sparse_fwd`
writes out, packed, the mask it computed from the indexer's operands, and
the backward kernels `sparse_dq` and `sparse_dkv` read it and compute no
index score.  The words' causal part is `keep_mask`'s int8 bit for bit, in
one and two chunks of words, at every width of tile, with tied scores and
with `topk` under and over the sequence; the gradients under the bits are
those of the same attention with the [S, S] scores whole; the backward
calls take no operand of the indexer's.  A file beside `test_keye.py` so
that the two run on two workers."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import seeded
from benchmark.tests import tiny_keye
import byteps_tpu as bps
from byteps_tpu.models import keye
from byteps_tpu.ops import sparse_attention as sa


def _operands(s, seed=0, heads=2, kv_heads=1, d=32, j=3, di=16):
    """q, k, v and an indexer's operands with INTEGER values: every index
    score is exact, and most rows' thresholds are tied."""
    k = jax.random.split(jax.random.key(seed), 6)
    q = jax.random.normal(k[0], (1, heads, s, d))
    kk, v = (jax.random.normal(k[i], (1, kv_heads, s, d)) for i in (1, 2))
    qi = jnp.round(jax.random.normal(k[3], (1, j, s, di)))
    ki = jnp.round(jax.random.normal(k[4], (1, s, di)))
    w = jnp.round(2 * jax.random.normal(k[5], (1, s, j)))
    return q, kk, v, qi, ki, w


def _unpacked(bits, s):
    """`bits` [B, S, W] int32 -> [B, S, S] bool, by the layout the module
    states: bit b of word [t, c * 128 + lane] is key c * 4096 + b * 128 +
    lane of row t."""
    bits = np.asarray(bits)
    out = np.zeros((bits.shape[0], s, s), bool)
    for k0 in range(0, s, 128):
        c, b = divmod(k0 // 128, 32)
        out[:, :, k0:k0 + 128] = bits[:, :, c * 128:(c + 1) * 128] >> b & 1
    return out


# (rows, block_k, topk): one chunk of words and two, the three widths of
# tile, `topk` under and over the rows
MASKS = [(256, 128, 64), (1024, 256, 2048), (1024, 512, 100),
         (4096, 512, 512), (8192, 512, 2048), (8192, 128, 700)]


@pytest.mark.parametrize("s,block_k,topk", MASKS)
def test_the_forward_kernels_bits_are_the_mask(s, block_k, topk):
    q, k, v, qi, ki, w = _operands(s)
    kit = ki.transpose(0, 2, 1)
    aux = sa.select(qi, kit, w, topk, block_k)
    keep = np.asarray(sa.keep_mask(qi, kit, aux, 128, block_k)).astype(bool)
    _, _, count, bits = sa._forward(q, k, v, qi, kit, aux, 1.0, 128, block_k,
                                    True)
    assert bits.shape == (1, s, sa.words(s)) and bits.dtype == jnp.int32
    assert sa.words(s) == 128 * -(-s // 4096)
    # words past a row block's diagonal are never written: the causal part
    causal = np.tril(np.ones((s, s), bool))
    assert ((_unpacked(bits, s) & causal) == keep).all()
    assert (keep.sum(-1) == np.minimum(np.arange(s) + 1, topk)).all()
    assert (np.asarray(count) == keep.sum(-1)).all()


@pytest.mark.parametrize("s,block_k,topk", [(1024, 256, 100),
                                            (4608, 512, 300)])
def test_gradients_under_the_bits_are_the_dense_ones(s, block_k, topk):
    """dq, dk and dv of the kernels, whose backward pass reads the bits,
    against `jax.grad` of the same layer with the scores whole, under the
    same selection: one chunk of words, and two."""
    q, k, v, qi, ki, w = _operands(s, seed=1, heads=4, kv_heads=2)
    g = jax.random.normal(jax.random.key(2), q.shape)

    def grads(f):
        return jax.grad(lambda *a: (f(*a, qi, ki, w, topk)[0] * g).sum(),
                        (0, 1, 2))(q, k, v)
    mine = grads(lambda *a: sa.selected_attention(*a, 128, block_k))
    plain = grads(sa.selected_attention_dense)
    for a, b in zip(mine, plain):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def _calls(jaxpr, found=None):
    """`{name: [(shape, dtype) of each operand]}` of every Pallas call in
    a jaxpr."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.setdefault(eqn.params["name"], []).append(
                [(tuple(v.aval.shape), str(v.aval.dtype))
                 for v in eqn.invars])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _calls(sub, found)
    return found


def test_the_backward_kernels_take_no_indexer_operand():
    s = 512
    q, k, v, qi, ki, w = _operands(s, heads=4, kv_heads=2)
    kit = ki.transpose(0, 2, 1)
    aux = sa.select(qi, kit, w, 64, 128)

    def loss(q, k, v):
        return sa.sparse_attention(q, k, v, qi, kit, aux, None, 128,
                                   128)[0].sum()
    calls = _calls(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, k, v).jaxpr)
    indexer = {(t.shape, str(t.dtype)) for t in (qi, kit, aux)}
    assert set(calls) == {"sparse_fwd", "sparse_dq", "sparse_dkv"}
    (fwd,), (dq,), (dkv,) = (calls[n] for n in ("sparse_fwd", "sparse_dq",
                                                "sparse_dkv"))
    # the table's three columns, then q, k, v and the indexer's three
    assert len(fwd) == 9 and indexer <= set(fwd)
    # the table, then q, k, v, do, lse, delta and the words
    for operands in (dq, dkv):
        assert len(operands) == 10
        assert not indexer & set(operands)
        assert operands[-1] == ((1, s, sa.words(s)), "int32")
    with pytest.raises(ValueError, match="must divide 4096"):
        sa.selected_attention(*_operands(1536), 64, 128, 384)


def _layer_step(dtype):
    """The tiny family cut to a scan over one period of one layer:
    `(cfg, rows of a sequence, params, loss_under)`, `loss_under(policy,
    remat)` the loss of the parameters under that remat policy."""
    family = tiny_keye.family(dtype, layers=[0, 1])
    cfg = family.cfg
    assert cfg.remat and cfg.remat_policy == "selection"
    params, batch = seeded.params(family, 0), seeded.batch(family, 0, 1)

    def loss_under(policy, remat=True):
        changed = dataclasses.replace(cfg, remat=remat, remat_policy=policy)
        return lambda p: keye.loss_fn(p, batch, changed)
    return cfg, family.seq_len, params, loss_under


def _kept_bytes(s, heads, kv_heads, d):
    """`o`, `lse` and `bits` of a sequence, from the forward rule's own
    residuals."""
    q, k, v, qi, ki, _ = _operands(s, heads=heads, kv_heads=kv_heads, d=d)
    aux = jax.ShapeDtypeStruct((1, s, sa.AUX_LANES), jnp.float32)
    _, residuals = jax.eval_shape(
        lambda *a: sa._sparse_fwd(*a, None, 0, 0, True), q, k, v, qi,
        ki.transpose(0, 2, 1), aux)
    return sum(t.size * t.dtype.itemsize for t in residuals[-3:])


@pytest.mark.parametrize("policy,calls", [
    ("selection", {"index_topk": 1, "sparse_fwd": 1, "sparse_dq": 1,
                   "sparse_dkv": 1}),
    ("none", {"index_topk": 2, "sparse_fwd": 2, "sparse_dq": 1,
              "sparse_dkv": 1})])
def test_a_layer_selects_once_and_scores_its_tiles_once(policy, calls):
    """Under the cell's remat policy a layer's step calls every kernel
    ONCE: the recomputed layer reads the `aux` its forward pass selected
    and the `o`, `lse` and `bits` its forward kernel wrote, by name.
    Under "none", the control, selection and forward kernel run twice.
    The gauges say who computes index scores, what the mask weighs and
    what the names keep."""
    cfg, rows, params, loss_under = _layer_step(jnp.float32)
    text = str(jax.make_jaxpr(jax.grad(loss_under(policy)))(params))
    # a scan over one period of one layer: calls a layer
    assert {name: text.count(f"name={name}") for name in calls} == calls
    metrics = bps.get_metrics()
    assert metrics["bps_sparse_index_passes"] == 1
    # a bit of the threshold a pass, then one pass or a bit of the cut's
    assert (metrics["bps_sparse_select_passes_min"],
            metrics["bps_sparse_select_passes_max"]) == (
                33, 32 + (rows - 1).bit_length())
    assert metrics["bps_sparse_rows"] == rows
    # every row block's words up to its diagonal's chunk
    assert metrics["bps_sparse_mask_bytes"] == sum(
        (q0 + 127) // 4096 + 1 for q0 in range(0, rows, 128)) * 128 * 128 * 4
    assert metrics["bps_sparse_kept_bytes"] == _kept_bytes(
        rows, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_gradients_are_the_same_bits_whatever_is_kept(dtype):
    """The same kernels on the same operands: every gradient leaf under
    "selection" (each kernel once) equals, bit for bit, the one under
    "none" (selection and forward kernel twice) and the one with no
    rematerialisation at all."""
    _, _, params, loss_under = _layer_step(dtype)
    kept, twice, whole = (
        jax.tree.leaves(jax.jit(jax.grad(loss_under(policy, remat)))(params))
        for policy, remat in (("selection", True), ("none", True),
                              ("none", False)))
    assert len(kept) > 3 and any(np.asarray(leaf).any() for leaf in kept)
    for other in (twice, whole):
        for mine, theirs in zip(kept, other):
            assert mine.dtype == theirs.dtype
            np.testing.assert_array_equal(np.asarray(mine),
                                          np.asarray(theirs))


def test_what_a_call_keeps_at_the_cells_shapes():
    """`bps_sparse_kept_bytes` at 32,768 rows of 32 bfloat16 heads of 128:
    o 268,435,456 + lse 4,194,304 + bits 134,217,728, from the shapes
    alone, and as the module's docstring reckons it."""
    shapes = {"q": (1, 32, 32768, 128), "kv": (1, 4, 32768, 128),
              "qi": (1, 16, 32768, 64), "ki": (1, 32768, 64),
              "w": (1, 32768, 16)}
    q, kv, qi, ki, w = (jax.ShapeDtypeStruct(shapes[n], jnp.bfloat16)
                        for n in ("q", "kv", "qi", "ki", "w"))
    o, count = jax.eval_shape(
        lambda *a: sa.selected_attention(*a, 2048), q, kv, kv, qi, ki, w)
    assert o.shape == q.shape and count.shape == (1, 32768)
    assert bps.get_metrics()["bps_sparse_kept_bytes"] == 406_847_488
    assert 32768 * (32 * 128 * 2 + 32 * 4 + 32768 // 8) == 406_847_488
