"""The granitemoehybrid program broken in fourteen ways
(`benchmark/tests/granitehybrid_variants.py`) at tiny widths in float32,
where the program as it is IS the reference up to rounding: each variant
leaves at least one of the comparisons that decide `correct`.  A file
beside `test_granite_hybrid.py` so that the two run on two workers."""

import pytest

from benchmark.families import granitehybrid as family_granite
from benchmark.tests import granitehybrid_variants as variants
from benchmark.tests import tiny_granitehybrid
from family_cases import Cases

CASES = Cases(tiny_granitehybrid, family_granite.Family)
# The layers a variant runs on: the mamba layer 4 for what breaks the
# scan, the convolution or the mixer; the attention layer 5 for what
# breaks it and for the multipliers every layer meets.
MAMBA, ATTENTION = [4], [5]
HELD = {
    # a mamba layer before and after an attention layer
    None: [4, 5, 6],
    "state_in_bfloat16": MAMBA,
    "state_dropped_at_chunk_edge": MAMBA,
    "cumulative_sum_in_bfloat16": MAMBA,
    "dt_bias_left_out": MAMBA,
    "softplus_left_out": MAMBA,
    "d_left_out": MAMBA,
    "conv_bias_left_out": MAMBA,
    "conv_shifted_by_one": MAMBA,
    "gate_after_norm": MAMBA,
    "embedding_multiplier_left_out": ATTENTION,
    "residual_multiplier_left_out": ATTENTION,
    "attention_multiplier_left_out": ATTENTION,
    "logits_scaling_left_out": ATTENTION,
    "attention_scale_sqrt": ATTENTION,
}


@pytest.mark.parametrize("variant", [None, *variants.VARIANTS])
def test_broken_variant_fails(variant):
    CASES.broken_variant_fails(variants.VARIANTS, variant, HELD[variant])
