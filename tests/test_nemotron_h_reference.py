"""The nemotron_h program at tiny widths against its plain float32 reference
(`benchmark/reference/nemotronh.py`), through the benchmark's own family and
comparison: the loss and every gradient leaf, on the cuts of the model the
table names.  A file beside `test_nemotron_h.py`: the two together are over
what a file may cost (`tools/check_test_budget.py`)."""

import jax.numpy as jnp
import pytest

from benchmark.tests import tiny_nemotronh
from family_cases import Cases

CASES = Cases(tiny_nemotronh)

# (layers of the model that are run, experts held)
CUTS = {
    "the_cells_nine_layers": (None, None),
    "one_of_each_kind": ([4, 5, 6], None),
    "whole_layers_every_expert": ([5, 6, 7], range(128)),
}
# The nine's float32 twin (83 s beside five other workers): of the cell's
# `MEMEM*EME` the attention layer and the LAST mamba and expert layers.
FLOAT32_AT_DEPTH = [5, 7, 8]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("cut", CUTS)
def test_against_reference(cut, dtype):
    """In float32: 1e-5 on the loss, 2e-4 on the worst leaf, no token's
    choice swapped."""
    layers, experts = CUTS[cut]
    family, got = CASES.against_reference(dtype, FLOAT32_AT_DEPTH,
                                          layers=layers, experts=experts)
    if dtype == jnp.float32:
        assert got["loss_rel_diff"] <= 1e-5
        assert got["worst_grad_rel_diff"] <= 1e-4, got
        assert all(s["swapped_share"] == 0 for s in family.selection)
        parts = family.selection[-1]
        for name in ("scan_rel_diff", "router_rel_diff", "experts_rel_diff",
                     "attn_row_diff"):
            assert parts[name] < 1e-5, (name, parts[name])
    if experts is not None:
        # every pair falls on a held expert: 6 rows a token in the one
        # expert layer of the three
        assert family.routing_counters[-1]["held_rows_per_token"] == [6.0]
