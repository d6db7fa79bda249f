"""The nemotron_h program broken in twelve ways
(`benchmark/tests/nemotronh_variants.py`) at tiny widths in float32, where
the program as it is IS the reference up to rounding: each variant leaves
at least one of the comparisons that decide `correct`, and the comparisons
of single parts tell the variants that break THEM.  A file beside
`test_nemotron_h.py` so that the two run on two workers."""

import jax.numpy as jnp
import pytest

from benchmark.harness import correct
from benchmark.tests import nemotronh_variants, tiny_nemotronh

_family, _agreement = tiny_nemotronh.family, tiny_nemotronh.agreement


@pytest.fixture(scope="module")
def float32_family():
    # a layer of each kind is all the variants need
    return _family(jnp.float32, tiny_nemotronh.FLOAT32, layers=[4, 5, 6])


@pytest.mark.parametrize("variant", [None, *nemotronh_variants.VARIANTS])
def test_broken_variant_fails(float32_family, variant):
    family = float32_family
    if variant is None:
        got = _agreement(family)
        assert correct.agreement_ok(got, family.reference_check), got
        return
    with nemotronh_variants.VARIANTS[variant](family):
        got = _agreement(family)
    assert not correct.agreement_ok(got, family.reference_check), got
    # The parts alone, on the step's own operands, equal on both sides:
    # each tells the variants that break IT, whatever the choice does.
    parts = family.selection[-1]
    told = {
        "scan_rel_diff": (family.scan_rel_tol, {
            "one_group_for_all_heads", "state_in_bfloat16"}),
        "router_rel_diff": (family.router_rel_tol, {
            "route_scale_left_out", "weights_not_normed"}),
        # the router's weights scale what the experts add
        "experts_rel_diff": (family.experts_rel_tol, {
            "silu_gate_for_relu2", "relu_without_the_square",
            "route_scale_left_out", "weights_not_normed",
            "expert_products_in_float8", "last_columns_dropped"}),
        "attn_row_diff": (family.attn_row_tol, {
            "softmax_stats_in_bfloat16"}),
    }
    for name, (limit, variants) in told.items():
        assert (parts[name] > limit) == (variant in variants), (
            name, parts[name])
    assert set(nemotronh_variants.ONLY_ROUNDING) <= set(
        nemotronh_variants.VARIANTS)
