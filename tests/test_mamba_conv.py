"""`ops/short_conv.py mamba_conv`, the Mamba-2 mixers' operator (taps, a
bias and a silu, one Pallas kernel each way), in the Pallas interpreter
against `jax.nn.silu(ssd.causal_conv1d(x, w, b))` and `jax.grad` of it:
values and all three gradients, over the shapes that meet the kernels'
edges.  `gated_short_conv`, the other operator of the module's walk, has
its tests in `tests/test_lfm2.py`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import byteps_tpu as bps
from byteps_tpu.ops import short_conv, ssd


def _oracle(x, w, b):
    return jax.nn.silu(ssd.causal_conv1d(x, w, b))


def _operands(batch, seq_len, width, taps, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    bound = 1.0 / np.sqrt(taps)
    return (jax.random.normal(ks[0], (batch, seq_len, width), dtype),
            jax.random.uniform(ks[1], (taps, width), jnp.float32,
                               -bound, bound),
            jax.random.uniform(ks[2], (width,), jnp.float32, -bound, bound),
            jax.random.normal(ks[3], (batch, seq_len, width), dtype))


def _both(x, w, b, g, parts, block_rows):
    """`(y, (dx, dw, db))` of the kernels and of the oracle in float32."""
    def kernel(x, w, b):
        y = short_conv.mamba_conv(x, w, b, parts=parts,
                                  block_rows=block_rows)
        return jnp.concatenate(y, -1) if parts else y
    y, vjp = jax.vjp(kernel, x, w, b)
    want, vjp32 = jax.vjp(_oracle, x.astype(jnp.float32), w, b)
    return (y, vjp(g)), (want, vjp32(g.astype(jnp.float32)))


# (batch, rows a sequence, width, taps, parts, rows a block); where the
# rows of a block divide a sequence's the kernels take their other path
# (no mask a row: a tile's edge rows are zeros at a sequence's ends)
SHAPES = {
    "ragged_last_block": (1, 100, 256, 4, None, 32),
    "two_sequences_a_block_across_them": (2, 48, 128, 4, None, 32),
    "two_sequences_in_one_block": (2, 40, 256, 4, (128, 64, 64), 0),
    "a_width_no_multiple_of_128": (2, 40, 24, 4, None, 16),
    "parts_no_multiple_of_128": (1, 64, 48, 4, (32, 8, 8), 16),
    "two_taps": (2, 48, 128, 2, None, 16),
    "one_tap": (1, 32, 128, 1, None, 16),
    "seven_taps": (1, 64, 128, 7, None, 16),
    "granite_width": (1, 256, 4352, 4, (4096, 128, 128), 128),
    "granite_width_one_result": (1, 272, 4352, 4, None, 0),
    "nemotron_width": (2, 128, 6144, 4, (4096, 1024, 1024), 128),
    "nemotron_width_ragged": (2, 136, 6144, 4, (4096, 1024, 1024), 128),
}


@pytest.mark.parametrize("shape", SHAPES)
def test_float32_is_the_oracle_to_1e_5(shape):
    batch, seq_len, width, taps, parts, block_rows = SHAPES[shape]
    x, w, b, g = _operands(batch, seq_len, width, taps, jnp.float32)
    (y, grads), (want, want_grads) = _both(x, w, b, g, parts, block_rows)
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    for name, got, ref in zip(("dx", "dw", "dbias"), grads, want_grads):
        assert got.shape == ref.shape and got.dtype == ref.dtype, name
        scale = float(jnp.abs(ref).max())
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5 * scale,
                                   err_msg=name)


@pytest.mark.parametrize("shape", [
    "ragged_last_block", "two_sequences_a_block_across_them",
    "a_width_no_multiple_of_128", "two_taps", "granite_width",
    "nemotron_width_ragged"])
def test_bfloat16_is_the_float32_result_rounded_once(shape):
    """Products, sums, bias and the silu in float32, ONE rounding: an
    element is within half a bfloat16 step of the float32 oracle on the
    same bfloat16 operands (the jnp form rounds the sum, then the silu);
    the taps' and bias's gradients are float32 sums and agree to 1e-5."""
    batch, seq_len, width, taps, parts, block_rows = SHAPES[shape]
    x, w, b, g = _operands(batch, seq_len, width, taps, jnp.bfloat16)
    (y, grads), (want, want_grads) = _both(x, w, b, g, parts, block_rows)
    assert y.dtype == grads[0].dtype == jnp.bfloat16
    assert grads[1].dtype == grads[2].dtype == jnp.float32

    def half_a_step(got, ref):
        # bfloat16 keeps 8 bits: half a step is at most 2^-8 of the
        # number; the rest is float32's own, a sum in another order (an
        # element that cancels to 1e-6, or falls the other side of a tie)
        err = jnp.abs(got.astype(jnp.float32) - ref)
        room = 1e-6 * float(jnp.abs(ref).max())
        return bool(jnp.all(err <= jnp.abs(ref) * 2.0 ** -8 * 1.01 + room))
    assert half_a_step(y, want)
    assert half_a_step(grads[0], want_grads[0])
    for got, ref in zip(grads[1:], want_grads[1:]):
        scale = float(jnp.abs(ref).max())
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5 * scale)


@pytest.mark.parametrize("block_rows", [16, 32, 0])
def test_no_tap_crosses_a_sequences_start_and_no_gradient_its_end(
        block_rows):
    """A batch of two is each sequence alone: values and the input's
    gradient to float32's last bits, whatever block the boundary falls
    in (a tap or a gradient across it would show at 1e-1)."""
    x, w, b, g = _operands(2, 40, 128, 4, jnp.float32, seed=3)

    def run(x, g):
        y, vjp = jax.vjp(lambda x: short_conv.mamba_conv(
            x, w, b, block_rows=block_rows), x)
        return y, vjp(g)[0]
    y, dx = run(x, g)
    for i in range(2):
        yi, dxi = run(x[i:i + 1], g[i:i + 1])
        np.testing.assert_allclose(y[i:i + 1], yi, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(dx[i:i + 1], dxi, rtol=1e-6, atol=1e-6)
    # the first K - 1 positions see zeros before them: position 0 is the
    # last tap and the bias alone
    np.testing.assert_allclose(y[:, 0], jax.nn.silu(x[:, 0] * w[3] + b),
                               rtol=1e-6, atol=1e-6)


def test_the_parts_are_the_results_lanes():
    x, w, b, _ = _operands(2, 48, 384, 4, jnp.float32, seed=5)
    whole = short_conv.mamba_conv(x, w, b)
    parts = short_conv.mamba_conv(x, w, b, parts=(256, 64, 64))
    assert [p.shape for p in parts] == [(2, 48, 256), (2, 48, 64),
                                        (2, 48, 64)]
    np.testing.assert_array_equal(jnp.concatenate(parts, -1), whole)


@pytest.mark.parametrize("what,x,w,b,parts", [
    ("taps_of_another_width", (1, 16, 128), (4, 64), (128,), None),
    ("bias_of_another_width", (1, 16, 128), (4, 128), (64,), None),
    ("parts_that_do_not_sum", (1, 16, 128), (4, 128), (128,), (64, 32)),
    ("eight_taps", (1, 16, 128), (8, 128), (128,), None),
])
def test_operands_that_do_not_fit_are_refused(what, x, w, b, parts):
    with pytest.raises(ValueError, match="do not fit"):
        short_conv.mamba_conv(jnp.zeros(x), jnp.zeros(w), jnp.zeros(b),
                              parts=parts)


def test_two_calls_under_their_names_with_2d_results_and_gauges():
    """What the device trace and the benchmark's readers see: one call
    named `mamba_conv_fwd` and one `mamba_conv_bwd`, every result 2-D
    (`flash_cost.classify` takes 3-D results for a flash kernel), no name
    another reader's pattern finds; and the gauges say the kernel ran and
    on how many rows a grid step."""
    from benchmark.reduce import conv_cost, ssd_cost
    x, w, b, g = _operands(2, 64, 256, 4, jnp.bfloat16)

    def both(x, w, b, g):
        y, vjp = jax.vjp(lambda *a: short_conv.mamba_conv(
            *a, parts=(128, 64, 64), block_rows=32), x, w, b)
        return y, vjp(tuple(jnp.split(g, [128, 192], axis=-1)))
    jaxpr = jax.make_jaxpr(both)(x, w, b, g)

    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)
    found = {eqn.params["name"]: eqn
             for eqn in calls(jaxpr.jaxpr)}
    assert sorted(found) == ["mamba_conv_bwd", "mamba_conv_fwd"]
    for name, eqn in found.items():
        assert all(v.aval.ndim == 2 for v in eqn.outvars), name
        assert not name.startswith("short_conv_")
        assert "ssd_fwd_c" not in name and "ssd_bwd_c" not in name
    assert [v.aval.shape for v in found["mamba_conv_fwd"].outvars] == [
        (128, 128), (128, 64), (128, 64)]
    assert [v.aval.shape for v in found["mamba_conv_bwd"].outvars] == [
        (128, 256), (8, 256)]
    line = ('%mamba_conv_fwd.1 = bf16[8192,4352]{1,0} custom-call('
            'bf16[8192,4352]{1,0} %a, bf16[8192,4352]{1,0} %a, '
            'f32[8,4352]{1,0} %w), custom_call_target="tpu_custom_call"')
    assert conv_cost.call(line) is None
    assert ssd_cost.scan_call(line) is None
    metrics = bps.get_metrics()
    assert metrics["bps_mamba_conv_kernel"] == 1
    assert metrics['bps_mamba_conv_rows{call="fwd"}'] == 32
    assert metrics['bps_mamba_conv_rows{call="bwd"}'] == 32


def test_the_rows_a_block_takes_follow_the_rule():
    """128 rows a grid step and chunks of 128 lanes at both cells' widths;
    a part that lane tiles do not divide is one chunk."""
    x, w, b, _ = _operands(1, 512, 128, 4, jnp.bfloat16)
    jax.jit(lambda *a: short_conv.mamba_conv(*a)).lower(x, w, b)
    assert bps.get_metrics()['bps_mamba_conv_rows{call="fwd"}'] == 128
    assert short_conv._blocks(100, 32)[:2] == (32, 4)
    assert short_conv._part_chunks((4096, 128, 128)) == [
        (0, 128, 32), (4096, 128, 1), (4224, 128, 1)]
    assert short_conv._part_chunks((4096, 1024, 1024)) == [
        (0, 128, 32), (4096, 128, 8), (5120, 128, 8)]
    assert short_conv._part_chunks((4352,)) == [(0, 128, 34)]
    assert short_conv._part_chunks((32, 8, 8)) == [
        (0, 32, 1), (32, 8, 1), (40, 8, 1)]
