"""The keye program at tiny widths against its plain float32 reference
(`benchmark/reference/keye.py`), through the benchmark's own family and
comparison: the loss and every gradient leaf, on the cuts of the model the
table names.  A file beside `test_keye.py`: the two together are over what
a file may cost (`tools/check_test_budget.py`)."""

import jax.numpy as jnp
import pytest

from benchmark.tests import tiny_keye
from family_cases import Cases

CASES = Cases(tiny_keye)

# (layers of the model that are run, experts held)
CUTS = {
    "one_layer": ([0], None),
    "the_cells_four_layers": (None, None),
    "whole_model_two_layers": ([2, 3], range(128)),
}
# The four's float32 twin (70 s beside five other workers): the four are
# of one kind, so the LAST of them.
FLOAT32_AT_DEPTH = [3]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("cut", CUTS)
def test_against_reference(cut, dtype):
    """In float32 the program's selection is the reference's own in every
    row."""
    layers, experts = CUTS[cut]
    family, got = CASES.against_reference(dtype, FLOAT32_AT_DEPTH,
                                          layers=layers, experts=experts)
    for record in family.selection:
        assert record["miscounted_rows"] == 0
        assert record["unexplained_rows"] == 0
        if dtype == jnp.float32:
            assert record["swapped_share"] == 0
            assert record["key_swapped_share"] == 0
            assert got["worst_grad_rel_diff"] < 1e-5
            assert got["loss_rel_diff"] < 1e-6
