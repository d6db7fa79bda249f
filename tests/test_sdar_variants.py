"""The sdar program broken in six ways
(`benchmark/tests/sdarmoe_variants.py`) at tiny widths in float32, where
the program as it is IS the reference up to rounding: each variant leaves
at least one of the comparisons that decide `correct`, and the comparisons
of single parts tell the variants that break THEM.  Every layer is of one
kind, so every variant runs on ONE layer, the first."""

import pytest

from benchmark.families import sdarmoe as family_sdarmoe
from benchmark.tests import sdarmoe_variants, tiny_sdarmoe
from family_cases import Cases

CASES = Cases(tiny_sdarmoe, family_sdarmoe.Family)
# The layers a variant runs on: the model's first, whatever it breaks.
HELD = {variant: [0] for variant in (None, *sdarmoe_variants.VARIANTS)}
TOLD = {
    "router_rel_diff": ("router_rel_tol", {"router_in_bfloat16"}),
    # the router's weights scale what the experts add
    "experts_rel_diff": ("experts_rel_tol", {"router_in_bfloat16"}),
    "attn_row_diff": ("attn_row_tol", {
        "noised_rows_see_their_own_clean_block",
        "block_diagonal_made_causal"}),
}


@pytest.mark.parametrize("variant", HELD)
def test_broken_variant_fails(variant):
    family, _ = CASES.broken_variant_fails(
        sdarmoe_variants.VARIANTS, variant, HELD[variant], TOLD)
    if variant is None:
        parts = family.selection[-1]
        assert parts["router_rel_diff"] < 1e-5
        assert parts["experts_rel_diff"] < 1e-5
        assert parts["attn_row_diff"] < 1e-5
