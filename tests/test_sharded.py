"""GSPMD sharded-training path (parallel/sharded.py), incl. ZeRO-1.

The reference's only strategy is DP with hand-built communication
(SURVEY §2.6); the GSPMD path is the TPU-idiomatic generalisation, and
ZeRO-1 optimizer-state sharding is the weight-update-sharding technique
(PAPERS.md) that plain DP lacks — these tests pin both to the local
single-device trajectory.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import byteps_tpu as bps
from byteps_tpu.models import transformer as tfm
from byteps_tpu.parallel import sharded


def _tiny():
    cfg = tfm.get_config("tiny", causal=True, remat=False,
                         dtype=jnp.float32)
    params = tfm.init_params(jax.random.key(0), cfg)
    toks, tgts = tfm.synthetic_batch(jax.random.key(1), 16, 32, cfg)

    def loss_fn(p, b):
        return tfm.loss_fn(p, b, cfg)
    return cfg, params, (toks, tgts), loss_fn


def _local_trajectory(params, batch, loss_fn, opt, n):
    @jax.jit
    def step(p, s, b):
        loss, g = jax.value_and_grad(loss_fn)(p, b)
        u, s = opt.update(g, s, p)
        return optax.apply_updates(p, u), s, loss

    s = opt.init(params)
    losses = []
    for _ in range(n):
        params, s, loss = step(params, s, batch)
        losses.append(float(loss))
    return losses


@pytest.mark.parametrize("zero1", [False, True])
def test_sharded_step_matches_local(mesh8, zero1):
    cfg, params, batch, loss_fn = _tiny()
    opt = optax.adamw(1e-3)
    specs = jax.tree.map(lambda _: P(), params)
    step = sharded.build_sharded_train_step(
        loss_fn, opt, mesh8, specs, zero1=zero1,
        params=params if zero1 else None)
    want = _local_trajectory(params, batch, loss_fn, opt, 4)

    # Committed, GSPMD-placed params — the deployment pattern (a bare
    # host tree would mask the in_shardings contract zero1_init exists
    # to satisfy).
    p = sharded.shard_params(params, mesh8, specs)
    s = (sharded.zero1_init(opt, p, mesh8, specs) if zero1
         else opt.init(p))
    got = []
    for _ in range(4):
        p, s, loss = step(p, s, batch)
        got.append(float(loss))
    np.testing.assert_allclose(got, want, rtol=2e-4)
    if zero1:
        # The returned state must actually live dp-sharded: adam moments
        # of the big embed table carry 'dp' in their sharding spec.
        mu_leaves = [l for l in jax.tree.leaves(s)
                     if hasattr(l, "sharding") and l.size >= 1024]
        assert mu_leaves, "no large opt-state leaves returned"
        assert any("dp" in (l.sharding.spec or ()) for l in mu_leaves), \
            [l.sharding for l in mu_leaves]


def test_zero1_specs_shard_moments_not_scalars(mesh8):
    cfg, params, batch, loss_fn = _tiny()
    opt = optax.adamw(1e-3)
    specs = jax.tree.map(lambda _: P(), params)
    z = sharded.zero1_opt_specs(opt, params, mesh8, specs)
    state_shape = jax.eval_shape(opt.init, params)
    flat_specs = jax.tree.leaves(
        z, is_leaf=lambda x: isinstance(x, P))
    flat_shapes = jax.tree.leaves(state_shape)
    assert len(flat_specs) == len(flat_shapes)
    for spec, leaf in zip(flat_specs, flat_shapes):
        names = {a for e in spec if e is not None
                 for a in (e if isinstance(e, tuple) else (e,))}
        if leaf.size < 1024:
            assert "dp" not in names, (spec, leaf.shape)
        if "dp" in names:
            ax = next(i for i, e in enumerate(spec)
                      if e == "dp" or (isinstance(e, tuple) and "dp" in e))
            assert leaf.shape[ax] % mesh8.shape["dp"] == 0


def test_zero1_respects_existing_dp_sharding(mesh8):
    """A leaf whose param spec already uses dp must not double-shard."""
    cfg, params, batch, loss_fn = _tiny()
    opt = optax.sgd(1e-2, momentum=0.9)
    specs = jax.tree.map(lambda _: P(), params)
    # Pretend the embed table is already dp-sharded (fsdp-style).
    specs = dict(specs)
    specs["embed"] = P("dp")
    z = sharded.zero1_opt_specs(opt, params, mesh8, specs)
    trace = jax.tree.flatten_with_path(
        z, is_leaf=lambda x: isinstance(x, P))[0]
    for path, spec in trace:
        if any(getattr(k, "key", None) == "embed" for k in path):
            flat = [a for e in spec if e is not None
                    for a in (e if isinstance(e, tuple) else (e,))]
            assert flat.count("dp") <= 1, (path, spec)


def test_zero1_requires_params():
    cfg, params, batch, loss_fn = _tiny()
    specs = jax.tree.map(lambda _: P(), params)
    with pytest.raises(TypeError, match="params"):
        bps.build_sharded_train_step(
            loss_fn, optax.adamw(1e-3),
            bps.make_mesh(), specs, zero1=True)


def test_fsdp_step_matches_local(mesh8):
    """FSDP (params sharded over dp) trains identically to the local
    step; the params and optimizer state actually live 1/dp per chip."""
    cfg, params, batch, loss_fn = _tiny()
    opt = optax.adamw(1e-3)
    fspecs = sharded.fsdp_param_specs(params, mesh8, min_shard_elems=64)
    names = {a for spec in jax.tree.leaves(
                 fspecs, is_leaf=lambda x: isinstance(x, P))
             for e in spec if e is not None
             for a in (e if isinstance(e, tuple) else (e,))}
    assert names == {"dp"}, names

    want = _local_trajectory(params, batch, loss_fn, opt, 4)
    p = sharded.shard_params(params, mesh8, fspecs)
    s = sharded.fsdp_init(opt, p, mesh8, fspecs)
    # Big leaves are genuinely partitioned: per-shard bytes < global.
    embed = p["embed"]
    assert embed.sharding.is_fully_replicated is False
    step = sharded.build_sharded_train_step(loss_fn, opt, mesh8, fspecs)
    got = []
    for _ in range(4):
        p, s, loss = step(p, s, batch)
        got.append(float(loss))
    np.testing.assert_allclose(got, want, rtol=2e-4)
    assert p["embed"].sharding.is_fully_replicated is False


def test_fsdp_composes_with_tp():
    """FSDP over dp composes with Megatron TP specs: tp-sharded dims are
    preserved and dp lands on a free dimension."""
    import byteps_tpu as bps
    cfg = tfm.get_config("llama_tiny")
    params = tfm.init_params(jax.random.key(0), cfg)
    mesh = bps.make_mesh(tp=2)   # dp=4, tp=2 on 8 devices
    base = tfm.param_specs(cfg)
    fspecs = sharded.fsdp_param_specs(params, mesh, base_specs=base,
                                      min_shard_elems=64)
    flat = jax.tree.flatten_with_path(
        fspecs, is_leaf=lambda x: isinstance(x, P))[0]
    seen_tp = seen_both = False
    for path, spec in flat:
        axes = [a for e in spec if e is not None
                for a in (e if isinstance(e, tuple) else (e,))]
        assert axes.count("dp") <= 1, (path, spec)
        if "tp" in axes:
            seen_tp = True
            if "dp" in axes:
                seen_both = True
    assert seen_tp, "TP specs were lost"
    assert seen_both, "no leaf carries both dp (FSDP) and tp"


def test_zero1_rejects_missing_axis():
    """A mesh without the named dp axis must raise, not silently no-op —
    on hierarchical meshes ('ici_dp'/'dcn_dp') a silent fallback would
    replicate the state the caller asked to shard."""
    import byteps_tpu as bps
    cfg, params, batch, loss_fn = _tiny()
    specs = jax.tree.map(lambda _: P(), params)
    opt = optax.adamw(1e-3)
    hmesh = bps.make_hierarchical_mesh(ici_size=4)
    with pytest.raises(ValueError, match="ici_dp"):
        sharded.zero1_opt_specs(opt, params, hmesh, specs)
    # Naming the axis explicitly works.
    z = sharded.zero1_opt_specs(opt, params, hmesh, specs,
                                dp_axis="ici_dp")
    names = {a for spec in jax.tree.leaves(
                 z, is_leaf=lambda x: isinstance(x, P))
             for e in spec if e is not None
             for a in (e if isinstance(e, tuple) else (e,))}
    assert "ici_dp" in names
