"""The ouro program at tiny widths against its plain float32 reference
(`benchmark/reference/ouro.py`), through the benchmark's own family and
comparison: the loss and every gradient leaf in the cell's bfloat16 and
in float32, on two of the cell's eight layers (all alike) walked the
model's four times."""

import jax.numpy as jnp
import pytest

from benchmark.families import ouro as family_ouro
from benchmark.tests import tiny_ouro
from family_cases import Cases

CASES = Cases(tiny_ouro, family_ouro.Family)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_against_reference(dtype):
    family, got = CASES.against_reference(dtype, layers=[0, 1])
    assert family.cfg.total_ut_steps == 4 and family.cfg.num_layers == 2
    assert got["worst_leaf"]
    parts = family.selection[-1]
    assert parts["exit_abs_diff"] <= family.exit_abs_tol
    assert parts["nll_rms_diff"] <= family.nll_rms_tol
