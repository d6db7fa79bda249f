"""The keye program broken in ten ways (`benchmark/tests/keye_variants.py`)
at tiny widths in float32, where the program as it is IS the reference up
to rounding: each variant leaves at least one of the comparisons that
decide `correct`, and the comparisons of single parts tell the variants
that break THEM.  A file beside `test_keye.py`; the six variants that
break the choice of keys are `test_keye_variants_selection.py`'s, which
takes `SELECTION` and `broken_variant_fails` from here: the twelve cases,
30 s each beside five other workers, are over what a file may cost."""

import pytest

from benchmark.families import keye as family_keye
from benchmark.tests import keye_variants, tiny_keye
from family_cases import Cases

CASES = Cases(tiny_keye)
# The layers a variant runs on: every layer of the model is of one kind
# (attention over selected keys, routed experts), so each holds one.
HELD = {None: [0], **dict.fromkeys(keye_variants.VARIANTS, [0])}
SELECTION = ("top2047", "selection_not_causal", "a_selection_a_head",
             "relu_left_out", "weights_left_out", "index_products_in_float8")
# the program as it is, on a text batch and on three streams that differ
STREAMS = {"as_it_is": None,
           "as_it_is_on_three_streams": family_keye.grid_positions}


@pytest.mark.parametrize("variant", [
    *STREAMS, *(v for v in keye_variants.VARIANTS if v not in SELECTION)])
def test_broken_variant_fails(variant):
    broken_variant_fails(variant)


def broken_variant_fails(variant):
    if variant in STREAMS:
        family = CASES.float32(HELD[None])
        family.positions = STREAMS[variant]
        try:
            CASES.broken_variant_fails(keye_variants.VARIANTS, None,
                                       HELD[None])
        finally:
            family.positions = None
        parts = family.selection[-1]
        assert parts["index_rel_diff"] < 1e-5
        assert parts["attn_row_diff"] < 1e-5
        assert parts["router_rel_diff"] < 1e-5
        assert parts["experts_rel_diff"] < 1e-5
        return
    family, _ = CASES.broken_variant_fails(keye_variants.VARIANTS, variant,
                                           HELD[variant])
    records = family.selection[-2:]
    told_by_the_choice = {"top2047", "relu_left_out", "weights_left_out",
                          "index_products_in_float8", "selection_not_causal"}
    if variant in told_by_the_choice:
        assert sum(r["unexplained_rows"] for r in records) > 0
    if variant in ("relu_left_out", "index_products_in_float8"):
        # the index scores alone, on operands equal on both sides
        assert records[-1]["index_rel_diff"] > family.limits["index_rel_tol"]
    if variant == "selection_not_causal":
        assert sum(r["miscounted_rows"] for r in records) > 0
    # the attention kernels alone, under a selection given to both sides
    assert (records[-1]["attn_row_diff"] > family.limits["attn_row_tol"]) == (
        variant in ("softmax_statistics_in_bfloat16", "short_rows_padded",
                    "all_visible_keys"))
