"""The mellum program at tiny widths against its plain float32 reference
(`benchmark/reference/mellum.py`), through the benchmark's own family and
comparison: the loss and every gradient leaf, on the cuts of the model the
table names.  A file beside `test_mellum.py`: the two together are what a
file may cost (`tools/check_test_budget.py`)."""

import jax.numpy as jnp
import pytest

from benchmark.families import mellum as family_mellum
from benchmark.tests import tiny_mellum
from byteps_tpu.models import afmoe
from family_cases import Cases

CASES = Cases(tiny_mellum, family_mellum.Family)

# (layers of the model that are run, experts held): layer 3 is full
# attention under YaRN, the others sliding under plain rotary positions.
CUTS = {
    "sliding": ([0], None),
    "full": ([3], None),
    "the_cells_four_layers": (None, None),
    "whole_model_two_layers": ([2, 3], range(64)),
}
# The four's float32 twin (66 s beside five other workers): of the cell's
# three sliding layers and a full one, the SECOND sliding one and the full.
FLOAT32_AT_DEPTH = [1, 3]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("cut", CUTS)
def test_against_reference(cut, dtype):
    layers, experts = CUTS[cut]
    family, got = CASES.against_reference(dtype, FLOAT32_AT_DEPTH,
                                          layers=layers, experts=experts)
    if layers is not None and len(layers) == 1:
        assert family.layer_types == (
            afmoe.FULL if cut == "full" else afmoe.SLIDING,)
    if dtype == jnp.float32:
        assert all(s["swapped_share"] == 0 for s in family.selection)
    if experts is not None:
        # every pair falls on a held expert: 8 rows a token a layer
        assert family.routing_counters[-1]["held_rows_per_token"] == [8.0,
                                                                      8.0]
