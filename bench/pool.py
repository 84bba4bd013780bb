"""The benchmark's traffic: a seeded pool of distinct batches.

A configuration's family (`bench/families/<family>.py`) says what a batch
holds and how one is drawn; this module owns the stream it is drawn from
and where it is placed. The pool is made on the device in one jitted call
from the seed and cut into batches of the global size (W workers x the
per-worker batch), each a dict of arrays placed where the launcher places
its batch: uncommitted arrays on the default device.
"""
from __future__ import annotations

from functools import partial

import jax
import numpy as np


def seed_key(seed: int):
    """A threefry key from any whole seed; equal to `jax.random.key(seed)`
    for 0 <= seed < 2**32, and distinct above it (where `key` truncates)."""
    seed %= 1 << 64
    data = np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(data, impl="threefry2x32")


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _pool(key, n_batches, rows, draw, args):
    keys = jax.random.split(key, n_batches)
    stack = jax.vmap(lambda k: draw(k, rows, *args))(keys)
    return tuple(jax.tree.map(lambda x: x[i], stack)
                 for i in range(n_batches))


def pool_key(seed: int):
    """The pool's own stream, apart from the trainer's key."""
    return jax.random.fold_in(seed_key(seed), 0x9001)


def make_pool(seed: int, n_batches: int, rows: int, draw, *args):
    """`n_batches` distinct batches of `rows` rows, as a list of dicts of
    device arrays: batch i is `draw(key_i, rows, *args)`, key_i the i-th
    split of the pool's stream. `draw` and `args` are static (hashable)."""
    return list(_pool(pool_key(seed), n_batches, rows, draw, args))
