"""Weights and batches from `--seed`, made on the device in one jitted
call each (so that a later run takes the program from the compile cache,
and nothing crosses from the host).  The same seed gives the same
arrays."""

from __future__ import annotations

import jax


def _keys(seed: int):
    return jax.random.split(jax.random.key(seed))


def params(family, seed: int):
    return jax.jit(family.init)(_keys(seed)[0])


def batch(family, seed: int, n_samples: int):
    return jax.jit(family.make_batch, static_argnums=1)(
        _keys(seed)[1], n_samples)


def shards(family, seed: int, n_samples: int, n_shards: int) -> list:
    """The batch of `n_samples` cut along its first axis into the pieces
    that `n_shards` chips would each get."""
    whole = batch(family, seed, n_samples)
    per = n_samples // n_shards
    return [jax.tree.map(lambda x: x[i * per:(i + 1) * per], whole)
            for i in range(n_shards)]
