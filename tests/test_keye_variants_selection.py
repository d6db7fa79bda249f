"""The six of the keye program's ten broken variants that break the choice
of keys (`test_keye_variants.py` has the case, the table and the other
four): a file of their own so that no file of the family's is most of a
worker's share."""

import pytest

from test_keye_variants import SELECTION, broken_variant_fails


@pytest.mark.parametrize("variant", SELECTION)
def test_broken_variant_fails(variant):
    broken_variant_fails(variant)
