"""The ouro program broken in eight ways
(`benchmark/tests/ouro_variants.py`) at tiny widths in float32, where the
program as it is IS the reference up to rounding: each variant leaves at
least one of the comparisons that decide `correct`, and the comparisons
of single parts tell the variants that break THEM.  Every layer is of one
kind, so every variant runs on ONE layer, walked twice."""

import pytest

from benchmark.families import ouro as family_ouro
from benchmark.tests import ouro_variants, tiny_ouro
from family_cases import Cases


class _TwoWalks:
    """`tiny_ouro` with the loop cut to two walks."""
    FLOAT32 = tiny_ouro.FLOAT32

    @staticmethod
    def config(layers=None):
        return tiny_ouro.config(layers=layers, walks=2)


CASES = Cases(_TwoWalks, family_ouro.Family)
# The layers a variant runs on: the model's first, whatever it breaks.
HELD = {variant: [0] for variant in (None, *ouro_variants.VARIANTS)}
TOLD = {
    "exit_abs_diff": ("exit_abs_tol", {"gate_in_bfloat16",
                                       "last_step_uses_its_gate"}),
    "nll_rms_diff": ("nll_rms_tol", {"logits_in_bfloat16"}),
}


@pytest.mark.parametrize("variant", HELD)
def test_broken_variant_fails(variant):
    family, got = CASES.broken_variant_fails(
        ouro_variants.VARIANTS, variant, HELD[variant], TOLD)
    if variant is None:
        parts = family.selection[-1]
        assert parts["exit_abs_diff"] < 1e-6
        assert parts["nll_rms_diff"] < 1e-5
    if variant == "weights_held_constant":
        # the loss itself is the program's: the gradients alone tell
        assert got["loss_rel_diff"] < 1e-6
        assert got["worst_leaf"] == "['exit_gate']"
