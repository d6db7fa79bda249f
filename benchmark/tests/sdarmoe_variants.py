"""The sdar program broken in six ways, each of which the cell's `correct`
has to catch (ISSUE 61, satellite (d)), and two controls (`CONTROLS`).  A variant is a context manager
over a family: inside it `family.loss` and the routing that
`family.reference_loss` asks the program for are the broken program's; the
reference stays what it is.

Every one needs the program's code patched, which is done here and
nowhere in the program.  Used by the tests at tiny widths
(`tests/test_sdar_variants.py`) and by `tools/reference_check.py` at the
published widths on the chip.
"""

import contextlib
from unittest import mock

import jax
import jax.numpy as jnp

from benchmark.tests.mellum_variants import (expert_products_in_float8,
                                             router_in_bfloat16,
                                             softmax_statistics_in_bfloat16)
from byteps_tpu.models import sdar
from byteps_tpu.ops import flash_attention


def _stretch(s, q0, k0, L, beta, q_axis):
    """What `flash_attention._bd_mask` computes before it compares: the
    keys' iota, the row's place in the tile, the row's block's first
    token counted in keys, and which copy the rows and the keys are."""
    shape = [1, 1]
    shape[q_axis] = s.shape[q_axis]
    row = jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    key = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    noised_q, noised_k = q0 >= L, k0 >= L
    at = row + (q0 - jnp.where(noised_q, L, 0)) - (
        k0 - jnp.where(noised_k, L, 0))
    return key, at, at - jax.lax.rem(row, beta), noised_q, noised_k


@contextlib.contextmanager
def noised_rows_see_their_own_clean_block(family):
    """Step 3's `b(c) < b(i)` made `<=`: a noised row reads the clean
    tokens it is asked to predict.  The table lists the diagonal tile of
    the clean copy for every block of noised rows, and its mask ends a
    block later."""
    def bd_tile(q0, k0, block_q, block_k, L, beta):
        if q0 >= L and k0 < L:
            q0 -= L                     # as a clean row sees them
        return tile(q0, k0, block_q, block_k, L, beta)

    def bd_mask(s, q0, k0, L, beta, q_axis=0):
        key, _, start, _, noised_k = _stretch(s, q0, k0, L, beta, q_axis)
        keep = ((key >= jnp.where(noised_k, start, 0))
                & (key < start + beta))
        return jnp.where(keep, s, flash_attention.NEG_INF)
    tile = flash_attention.bd_tile
    with mock.patch.object(flash_attention, "bd_tile", bd_tile), \
            mock.patch.object(flash_attention, "_bd_mask", bd_mask):
        yield family


@contextlib.contextmanager
def block_diagonal_made_causal(family):
    """A noised row sees the noised keys of its block up to ITSELF: the
    block's later tokens are hidden, as under a causal mask."""
    mask = flash_attention._bd_mask

    def bd_mask(s, q0, k0, L, beta, q_axis=0):
        key, at, _, noised_q, noised_k = _stretch(s, q0, k0, L, beta, q_axis)
        later = jnp.logical_and(noised_q, noised_k) & (key > at)
        return jnp.where(later, flash_attention.NEG_INF,
                         mask(s, q0, k0, L, beta, q_axis))
    with mock.patch.object(flash_attention, "_bd_mask", bd_mask):
        yield family


@contextlib.contextmanager
def _head_reads(change):
    """The head's loss on `change(batch)`."""
    head_loss = sdar.head_loss

    def changed(params, x, batch, cfg):
        return head_loss(params, x, change(batch), cfg)
    with mock.patch.object(sdar, "head_loss", changed):
        yield


@contextlib.contextmanager
def weight_left_out(family):
    """A masked token counts 1, not 1 / t."""
    with _head_reads(lambda b: (b[0], b[1], b[1].astype(jnp.float32))):
        yield family


@contextlib.contextmanager
def logits_shifted_by_one(family):
    """Token i's logits predict token i + 1, as a next-token loss does."""
    with _head_reads(lambda b: (jnp.roll(b[0], -1, axis=1), b[1], b[2])):
        yield family


@contextlib.contextmanager
def positions_run_on_through_the_noised_copy(family):
    """Row r at position r, not r mod L: a token and its noised copy turn
    L positions apart."""
    two_copies = sdar.two_copies

    def run_on(params, batch, cfg):
        x, positions = two_copies(params, batch, cfg)
        return x, jnp.arange(positions.shape[0], dtype=positions.dtype)
    with mock.patch.object(sdar, "two_copies", run_on):
        yield family


VARIANTS = {
    "router_in_bfloat16": router_in_bfloat16,
    "noised_rows_see_their_own_clean_block":
        noised_rows_see_their_own_clean_block,
    "block_diagonal_made_causal": block_diagonal_made_causal,
    "weight_left_out": weight_left_out,
    "logits_shifted_by_one": logits_shifted_by_one,
    "positions_run_on_through_the_noised_copy":
        positions_run_on_through_the_noised_copy,
}
# mellum's controls of the two limits that are a PRECISION's: the nearest
# one below the cell's.  `tools/reference_check.py --variants all` runs
# them after `VARIANTS`, the tier-1 tests do not
# (`benchmark/tests/test_sdarmoe.py` does).
CONTROLS = {
    "softmax_statistics_in_bfloat16": softmax_statistics_in_bfloat16,
    "expert_products_in_float8": expert_products_in_float8,
}
