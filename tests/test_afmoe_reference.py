"""The afmoe program at tiny widths against its plain float32 reference
(`benchmark/reference/afmoe.py`), through the benchmark's own family and
comparison: the loss and every gradient leaf, on the cuts of the model the
table names.  A file beside `test_afmoe.py`: the two together are what a
file may cost (`tools/check_test_budget.py`)."""

import jax.numpy as jnp
import pytest

from benchmark.families import afmoe as family_afmoe
from benchmark.tests import tiny_afmoe
from byteps_tpu.models import afmoe
from family_cases import Cases

CASES = Cases(tiny_afmoe, family_afmoe.Family)

# (layers of the model that are run, dense layers the model is said to
# have): layer 3 is full attention, and dense if the model has four.
LAYERS = {
    "dense_sliding": ([1], None),
    "dense_full": ([3], 4),
    "expert_sliding": ([4], None),
    "expert_full": ([7], None),
    "five_layer_stack": (None, None),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("layers", LAYERS)
def test_against_reference(layers, dtype):
    run, dense = LAYERS[layers]
    # (the stack in float32 too: its three expert sliding layers are one
    # scan, so no cut of it is cheaper than the 58 s it costs)
    family, _ = CASES.against_reference(dtype, layers=run,
                                        published_dense_layers=dense)
    kinds = {(i < family.cfg.num_dense_layers, t)
             for i, t in enumerate(family.layer_types)}
    if run is not None:
        assert kinds == {(layers.startswith("dense"),
                          afmoe.SLIDING if layers.endswith("sliding")
                          else afmoe.FULL)}
    if dtype == jnp.float32:
        assert all(s["swapped_share"] == 0 for s in family.selection)
