"""The mellum program broken in ten ways
(`benchmark/tests/mellum_variants.py`) at tiny widths in float32, where
the program as it is IS the reference up to rounding: each variant leaves
at least one of the comparisons that decide `correct`, and the comparisons
of single parts tell the variants that break THEM.  A file beside
`test_mellum.py` so that the two run on two workers."""

import pytest

from benchmark.families import mellum as family_mellum
from benchmark.tests import mellum_variants, tiny_mellum
from family_cases import Cases

CASES = Cases(tiny_mellum, family_mellum.Family)
# The layers a variant runs on: every layer routes to experts, layer 2
# attends under a sliding window and plain rotary positions, layer 3 to
# every key under YaRN.
SLIDING, FULL = [2], [3]
HELD = {
    None: [2, 3],
    "router_in_bfloat16": SLIDING,
    "softmax_statistics_in_bfloat16": SLIDING,
    "yarn_amplitude_left_out": FULL,
    "yarn_ramp_left_out": FULL,
    "window_off_by_one_tile": SLIDING,
    "norm_topk_prob_off": SLIDING,
    "held_expert_dropped": SLIDING,
    "expert_products_in_float8": SLIDING,
    "top7": SLIDING,
    "held_weight_not_held": SLIDING,
}
TOLD = {
    "router_rel_diff": ("router_rel_tol", {
        "router_in_bfloat16", "norm_topk_prob_off"}),
    # the router's weights scale what the experts add
    "experts_rel_diff": ("experts_rel_tol", {
        "expert_products_in_float8", "held_expert_dropped",
        "router_in_bfloat16", "norm_topk_prob_off"}),
    "attn_row_diff": ("attn_row_tol", {
        "softmax_statistics_in_bfloat16", "window_off_by_one_tile"}),
}


@pytest.mark.parametrize("variant", [None, *mellum_variants.VARIANTS])
def test_broken_variant_fails(variant):
    family, _ = CASES.broken_variant_fails(
        mellum_variants.VARIANTS, variant, HELD[variant], TOLD)
    if variant is None:
        parts = family.selection[-1]
        assert parts["router_rel_diff"] < 1e-5
        assert parts["experts_rel_diff"] < 1e-5
        assert parts["attn_row_diff"] < 1e-5
    if variant in ("top7", "router_in_bfloat16"):
        # caught by the choice, which rounding does not explain
        assert sum(s["unexplained_tokens"]
                   for s in family.selection[-2:]) > 0
