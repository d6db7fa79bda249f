"""The granitemoehybrid program at tiny widths against its plain float32
reference (`benchmark/reference/granitehybrid.py`), through the
benchmark's own family and comparison: the loss and every gradient leaf,
on the lists of layers the table names, a stage's ten in both dtypes (37-54
s beside five other workers).  A file beside `test_granite_hybrid.py`: the
two together are over what a file may cost (`tools/check_test_budget.py`)."""

import jax.numpy as jnp
import pytest

from benchmark.families import granitehybrid as family_granite
from benchmark.tests import tiny_granitehybrid
from byteps_tpu.models import granite_hybrid as gh
from family_cases import Cases

M, A = gh.MAMBA, gh.ATTENTION
CASES = Cases(tiny_granitehybrid, family_granite.Family)

# layers of the model that are run; 5, 15, 25, 35 are attention
LAYERS = {
    "one_mamba": [0],
    "one_attention": [5],
    "mamba_attention_mamba": [4, 5, 6],
    "a_later_stage": list(range(10, 20)),
    "the_cells_ten": None,
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("layers", LAYERS)
def test_against_reference(layers, dtype):
    family, _ = CASES.against_reference(dtype, layers=LAYERS[layers])
    if LAYERS[layers] is None:
        assert family.layer_types == (M,) * 5 + (A,) + (M,) * 4
