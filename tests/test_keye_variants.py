"""The keye program broken in ten ways (`benchmark/tests/keye_variants.py`)
at tiny widths in float32, where the program as it is IS the reference up
to rounding: each variant leaves at least one of the comparisons that
decide `correct`, and the comparisons of single parts tell the variants
that break THEM.  A file beside `test_keye.py` so that the two run on two
workers."""

import jax.numpy as jnp
import pytest

from benchmark.families import keye as family_keye
from benchmark.harness import correct
from benchmark.tests import keye_variants, tiny_keye

_family, _agreement = tiny_keye.family, tiny_keye.agreement


@pytest.fixture(scope="module")
def float32_family():
    return _family(jnp.float32, tiny_keye.FLOAT32, layers=[0, 1])


# the program as it is, on a text batch and on three streams that differ
STREAMS = {"as_it_is": None,
           "as_it_is_on_three_streams": family_keye.grid_positions}


@pytest.mark.parametrize("variant", [*STREAMS, *keye_variants.VARIANTS])
def test_broken_variant_fails(float32_family, variant):
    """Each way of breaking the program leaves at least one of the
    comparisons that decide `correct`; the program as it is passes all,
    with text positions and with three streams that differ."""
    family = float32_family
    if variant in STREAMS:
        family.positions = STREAMS[variant]
        try:
            got = _agreement(family)
        finally:
            family.positions = None
        assert correct.agreement_ok(got, family.reference_check), got
        parts = family.selection[-1]
        assert parts["index_rel_diff"] < 1e-5
        assert parts["attn_row_diff"] < 1e-5
        assert parts["router_rel_diff"] < 1e-5
        assert parts["experts_rel_diff"] < 1e-5
        return
    with keye_variants.VARIANTS[variant](family):
        got = _agreement(family)
    assert not correct.agreement_ok(got, family.reference_check), got
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
