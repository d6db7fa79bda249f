"""The sdar program at tiny widths against its plain float32 reference
(`benchmark/reference/sdarmoe.py`), through the benchmark's own family and
comparison: the loss and every gradient leaf in the cell's bfloat16, on
two of the cell's six layers (all alike) as a share and, one layer, as the
whole model.  The float32 twin is the unbroken case of
`test_sdar_variants.py`."""

import jax.numpy as jnp
import pytest

from benchmark.families import sdarmoe as family_sdarmoe
from benchmark.tests import tiny_sdarmoe
from family_cases import Cases

CASES = Cases(tiny_sdarmoe, family_sdarmoe.Family)
CUTS = {"share_two_layers": ([0, 1], None),
        "whole_model_one_layer": ([0], range(128))}


@pytest.mark.parametrize("cut", CUTS)
def test_against_reference(cut):
    layers, experts = CUTS[cut]
    family, got = CASES.against_reference(jnp.bfloat16, layers=layers,
                                          experts=experts)
    assert got["worst_leaf"]
    rows = family.routing_counters[-1]["held_rows_per_token"]
    if experts is not None:
        # every pair falls on a held expert: 8 a ROW of the two copies
        assert rows == [8.0]
    else:
        assert all(0.5 < r < 2.0 for r in rows)
