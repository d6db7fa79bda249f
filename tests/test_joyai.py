"""The joyai model (`byteps_tpu/models/joyai.py`: latent attention, a
sigmoid router with a shared expert behind one dense layer, a
multi-token-prediction module) at tiny widths in float32 against its plain
reference (`benchmark/reference/joyai.py`), through the benchmark's own
family and comparison: the loss, both losses apart and every gradient
leaf, the whole model and a share; the test that ties the sixteen shares
of an expert layer to the uncut layer; the parameter count of the cell
from the built tree; and the flash kernels with queries and keys of one
width and values of another.  (The ten broken variants run with the
benchmark's own tests, `benchmark/tests/test_joyai.py`.)"""

import dataclasses
import hashlib
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import correct, manifest, seeded
from benchmark.reference import joyai as reference
from benchmark.tests import tiny_joyai
from byteps_tpu.models import joyai
from byteps_tpu.ops.flash_attention import flash_attention
from byteps_tpu.parallel import dropless_moe


@pytest.mark.parametrize("experts", [None, range(256)],
                         ids=["share", "whole_model"])
def test_against_reference(experts):
    """In float32 the program IS the reference up to rounding: the dense
    layer, an expert layer and the prediction module's, as one chip's
    sixteen experts and with all 256 held; the two losses apart too."""
    family = tiny_joyai.family(jnp.float32, tiny_joyai.FLOAT32,
                               layers=[0, 1], experts=experts)
    assert (family.cfg.num_dense_layers, family.cfg.num_layers,
            family.cfg.num_mtp_modules) == (1, 2, 1)
    got = tiny_joyai.agreement(family)
    assert correct.agreement_ok(got, family.reference_check), got
    assert got["worst_leaf"] and family.selection[-1]["swapped_share"] == 0
    if experts is None:
        params = seeded.params(family, 0)
        batch = seeded.batch(family, 0, 2)
        mine, plain = jax.jit(family.losses)(params, batch)
        np.testing.assert_allclose(np.asarray(mine), np.asarray(plain),
                                   rtol=1e-6)
        assert float(mine[1]) > 0 and float(mine[0]) != float(mine[1])
        np.testing.assert_allclose(
            float(mine[0]) + family.cfg.mtp_loss_weight * float(mine[1]),
            got["loss"], rtol=1e-6)
        import byteps_tpu as bps
        metrics = bps.get_metrics()
        assert metrics['bps_loss_term_weight{loss="mtp"}'] == pytest.approx(
            0.3)
        assert metrics['bps_loss_term_positions{loss="mtp"}'] == 2 * 63
        assert metrics['bps_loss_term_positions{loss="main"}'] == 2 * 64


def test_the_shares_add_up_to_the_model():
    """Guide, section 4: over the sixteen chips that share a layer, the
    routed parts the shares compute plus the shared expert counted once
    are the uncut reference's expert layer, for the same tokens."""
    family = tiny_joyai.family(jnp.float32, layers=[1])
    cfg, spec = family.cfg, family.spec
    E, D, F = cfg.num_experts, cfg.hidden_size, cfg.moe_intermediate_size
    k = jax.random.split(jax.random.key(0), 8)
    whole = {
        "router_w": jax.random.normal(k[0], (D, E)) / 8,
        "expert_gate_w": jax.random.normal(k[1], (E, D, F)) / 8,
        "expert_up_w": jax.random.normal(k[2], (E, D, F)) / 8,
        "expert_down_w": jax.random.normal(k[3], (E, F, D)) / 6,
    }
    shared_w = [jax.random.normal(k[4], (D, F)) / 8,
                jax.random.normal(k[5], (D, F)) / 8,
                jax.random.normal(k[6], (F, D)) / 6]
    m = jax.random.normal(k[7], (96, D))
    with jax.default_matmul_precision("highest"):
        uncut, _ = reference.routed_experts(
            m, whole, {**spec, "held": tuple(range(E))})
        shared = reference.swiglu(m, *shared_w)

    @jax.jit
    def first_sixteen(router_w, experts):
        return dropless_moe.held_experts(
            m, router_w, experts,
            dataclasses.replace(cfg.moe, held=tuple(range(E // 16))))

    def share(chip):
        # chip c's sixteen experts moved to the front of the router's
        # columns: one program for the sixteen shares
        held = (jnp.arange(E // 16) + chip * (E // 16))
        out, routing = first_sixteen(
            jnp.roll(whole["router_w"], -chip * (E // 16), axis=1),
            {n: whole["expert_" + n][held]
             for n in ("gate_w", "up_w", "down_w")})
        return out, int(routing.held_rows)

    total, rows = shared, 0
    for chip in range(16):
        out, held_rows = share(chip)
        total, rows = total + out, rows + held_rows
    # and a share told its experts by their own ids is that share
    held = tuple(range(5 * E // 16, 6 * E // 16))
    by_ids, _ = dropless_moe.held_experts(
        m, whole["router_w"],
        {n: whole["expert_" + n][jnp.asarray(held)]
         for n in ("gate_w", "up_w", "down_w")},
        dataclasses.replace(cfg.moe, held=held))
    np.testing.assert_allclose(np.asarray(by_ids), np.asarray(share(5)[0]),
                               atol=1e-6)
    assert rows == m.shape[0] * cfg.num_experts_per_tok
    np.testing.assert_allclose(np.asarray(total), np.asarray(shared + uncut),
                               atol=2e-5, rtol=2e-5)


def test_the_cells_tree_counts_the_parameters_the_configuration_states():
    """`benchmark/configs/joyai-llm-flash.json` `deployment.parameters`,
    from the tree the cell's family builds (shapes alone)."""
    from benchmark.families import joyai as family_joyai
    with open(os.path.join(manifest.BENCH, "configs",
                           tiny_joyai.NAME + ".json")) as f:
        config = json.load(f)
    family = family_joyai.Family(config, config["job"])
    tree = jax.eval_shape(family.init, jax.random.key(0))

    def count(t):
        return sum(math.prod(a.shape) for a in jax.tree.leaves(t))
    attention = 26_347_520
    assert count({k: v for k, v in tree["dense"].items()
                  if not k.startswith("mlp_")}) == attention + 4_096
    assert count(tree["dense"]) == 70_391_808
    assert count(tree["moe"]) == 4 * 107_091_968
    assert count(tree["mtp"]) == 115_486_720
    assert count(tree) == 680_439_808
    assert "expert_bias" not in tree["moe"]
    assert f"{count(tree):,}" in config["deployment"]["parameters"]
    # what a step's tokens give a held expert: half its deployment load
    # (the configuration's `deployment.load` says why not all of it)
    tokens = config["job"]["per_chip_batch"] * config["job"]["seq_len"]
    assert tokens * family.cfg.num_experts_per_tok / 256 == 512
    assert family.cfg.moe.buffer_rows(tokens) == 10_240


def _dense(q, k, v):
    s = jnp.einsum("bqd,bkd->bqk", q, k) / math.sqrt(q.shape[-1])
    n = q.shape[1]
    p = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((n, n), bool)), s,
                                 -jnp.inf), -1)
    return jnp.einsum("bqk,bkd->bqd", p, v)


@pytest.mark.parametrize("streaming", [False, True],
                         ids=["resident", "streaming"])
def test_flash_takes_keys_of_192_and_values_of_128(streaming):
    """Forward and all three gradients against dense attention, the
    interpreter: q and k [BH, S, 192], v, o and dO [BH, S, 128]; two row
    blocks and two key tiles, so every kernel carries across tiles."""
    ks = jax.random.split(jax.random.key(0), 4)
    q, k = (jax.random.normal(key, (2, 256, 192)) for key in ks[:2])
    v, g = (jax.random.normal(key, (2, 256, 128)) for key in ks[2:])

    def flash(q, k, v):
        return flash_attention(q, k, v, True, None, 128, 128, True,
                               streaming)
    out, vjp = jax.vjp(flash, q, k, v)
    got = (out, *vjp(g))
    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(_dense, q, k, v)
        want = (out, *vjp(g))
    assert [a.shape[-1] for a in got] == [128, 192, 192, 128]
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
    with pytest.raises(ValueError, match="share a shape"):
        flash_attention(q, k[..., :128], v, True, None, 128, 128, True)


def _pallas_calls(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_calls(sub, found)
    return found


def _lowered(bh, s, dk, dv, block_q, block_k, streaming, window):
    q = jax.ShapeDtypeStruct((bh, s, dk), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((bh, s, dv), jnp.bfloat16)

    def grads(q, k, v):
        return jax.grad(lambda q, k, v: flash_attention(
            q, k, v, True, None, block_q, block_k, True, streaming,
            window).astype(jnp.float32).sum(), (0, 1, 2))(q, k, v)
    return jax.jit(grads), (q, q, v)


# (BH, S, D, block_q, block_k, streaming, window) -> the first 16 hex
# digits of sha256 over the call's lowered text (`_lowered`'s), AS
# THE TREE BEFORE THE KERNELS LEARNT A SECOND WIDTH lowered it (commit
# 477a616; a change to the kernels that means to change a one-width call
# writes its own here).
ONE_WIDTH_CALLS = {
    (2, 256, 64, 128, 256, None, None): "ba92861a4cd54701",
    (2, 512, 128, 128, 128, True, None): "7529d856fccf564e",
    (2, 512, 128, 128, 128, None, 256): "f22bf1768269b9a6",
    (2, 512, 128, 128, 128, True, 256): "5d39529748640bf5",
}


@pytest.mark.parametrize("call", ONE_WIDTH_CALLS,
                         ids=["resident", "streaming", "resident_window",
                              "streaming_window"])
def test_a_call_of_one_width_lowers_to_what_it_lowered_to(call):
    """Dk == Dv: the same text, and so the same kernels under the same
    names (none, or the window's) on the same blocks."""
    bh, s, d, block_q, block_k, streaming, window = call
    fn, args = _lowered(bh, s, d, d, block_q, block_k, streaming, window)
    text = fn.lower(*args).as_text()
    assert "loc(" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == (
        ONE_WIDTH_CALLS[call])
    names = {e.params["name"]
             for e in _pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr, [])}
    assert names == ({None} if window is None else {
        f"flash_{kind}_w256" for kind in ("fwd", "dq", "dkv")}), names


@pytest.mark.parametrize("streaming", [None, True])
def test_a_call_of_two_widths_says_both_in_its_kernels_names(streaming):
    """`flash_fwd_d192x128`, `flash_dq_d192x128`, `flash_dkv_d192x128`,
    each operand's block as wide as the operand: nothing is padded."""
    fn, args = _lowered(2, 512, 192, 128, 128, 128, streaming, None)
    calls = _pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr, [])
    names = sorted(e.params["name"] for e in calls)
    assert names == ["flash_dkv_d192x128", "flash_dq_d192x128",
                     "flash_fwd_d192x128"]
    for e in calls:
        def size(dim):
            return int(getattr(dim, "block_size", dim))
        wide = [size(m.block_shape[-1])
                for m in e.params["grid_mapping"].block_mappings
                if size(m.block_shape[1]) != 1]
        assert set(wide) == {192, 128}, wide
        assert wide.count(192) == {"fwd": 2, "dq": 3, "dkv": 3}[
            e.params["name"].split("_")[1]]


def test_streaming_is_chosen_by_both_widths_and_kept_bytes_by_the_values():
    """At the cell's 16,384 positions a head's K is 6 MiB and its V 4:
    over the resident budget together; `o` is as wide as V."""
    from byteps_tpu.ops import flash_attention as fa
    k = jax.ShapeDtypeStruct((64, 16384, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((64, 16384, 128), jnp.bfloat16)
    assert fa._use_streaming(k, None, v)
    short = jax.ShapeDtypeStruct((64, 8192, 192), jnp.bfloat16)
    assert not fa._use_streaming(short, None, v)     # 3 + 2 MiB
    wide = jax.ShapeDtypeStruct((64, 8192, 256), jnp.bfloat16)
    assert fa._use_streaming(short, None, wide)      # 3 + 4 MiB
    assert fa.kept_bytes(64, 16384, 128, jnp.bfloat16) == 64 * 16384 * 260
