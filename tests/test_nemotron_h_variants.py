"""The nemotron_h program broken in twelve ways
(`benchmark/tests/nemotronh_variants.py`) at tiny widths in float32, where
the program as it is IS the reference up to rounding: each variant leaves
at least one of the comparisons that decide `correct`, and the comparisons
of single parts tell the variants that break THEM.  A file beside
`test_nemotron_h.py` so that the two run on two workers."""

import pytest

from benchmark.tests import nemotronh_variants, tiny_nemotronh
from family_cases import Cases

CASES = Cases(tiny_nemotronh)
# The layers a variant runs on, of the model's `MEMEM*EME`: the expert
# layer 6 for what breaks the routing or the experts; the mamba layer 4
# or the attention layer 5 for what breaks those, WITH the expert layer,
# beside which alone the family records its parts.
EXPERT, MAMBA, ATTENTION = [6], [4, 6], [5, 6]
HELD = {
    None: [4, 5, 6],
    "silu_gate_for_relu2": EXPERT,
    "relu_without_the_square": EXPERT,
    "route_scale_left_out": EXPERT,
    "weights_not_normed": EXPERT,
    "one_group_for_all_heads": MAMBA,
    "norm_over_the_whole_width": MAMBA,
    "norm_before_the_gate": MAMBA,
    "state_in_bfloat16": MAMBA,
    "rotary_positions_applied": ATTENTION,
    "expert_products_in_float8": EXPERT,
    "last_columns_dropped": EXPERT,
    "softmax_stats_in_bfloat16": ATTENTION,
}
TOLD = {
    "scan_rel_diff": ("scan_rel_tol", {
        "one_group_for_all_heads", "state_in_bfloat16"}),
    "router_rel_diff": ("router_rel_tol", {
        "route_scale_left_out", "weights_not_normed"}),
    # the router's weights scale what the experts add
    "experts_rel_diff": ("experts_rel_tol", {
        "silu_gate_for_relu2", "relu_without_the_square",
        "route_scale_left_out", "weights_not_normed",
        "expert_products_in_float8", "last_columns_dropped"}),
    "attn_row_diff": ("attn_row_tol", {"softmax_stats_in_bfloat16"}),
}


@pytest.mark.parametrize("variant", [None, *nemotronh_variants.VARIANTS])
def test_broken_variant_fails(variant):
    CASES.broken_variant_fails(nemotronh_variants.VARIANTS, variant,
                               HELD[variant], TOLD)
    assert set(nemotronh_variants.ONLY_ROUNDING) <= set(
        nemotronh_variants.VARIANTS)
