"""The afmoe program broken in seven ways
(`benchmark/tests/afmoe_variants.py`) at tiny widths in float32, where the
program as it is IS the reference up to rounding: each variant leaves at
least one of the comparisons that decide `correct`.  A file beside
`test_afmoe.py` so that the two run on two workers."""

import pytest

from benchmark.families import afmoe as family_afmoe
from benchmark.tests import afmoe_variants, tiny_afmoe
from family_cases import Cases

CASES = Cases(tiny_afmoe, family_afmoe.Family)
# The layers a variant runs on: an expert layer that attends under a
# sliding window and rotary positions (4), or to every key without (7).
SLIDING, FULL = [4], [7]
HELD = {
    None: [4, 7],
    "window_dropped": SLIDING,
    "rope_in_full_layers": FULL,
    "gate_left_out": SLIDING,
    "top7": SLIDING,
    "route_scale_left_out": SLIDING,
    "router_in_bfloat16": SLIDING,
    "held_rows_dropped": SLIDING,
}


@pytest.mark.parametrize("variant", [None, *afmoe_variants.VARIANTS])
def test_broken_variant_fails(variant):
    family, _ = CASES.broken_variant_fails(afmoe_variants.VARIANTS, variant,
                                           HELD[variant])
    if variant in ("top7", "router_in_bfloat16"):
        # caught by the choice, which rounding does not explain
        assert sum(s["unexplained_tokens"]
                   for s in family.selection[-2:]) > 0
