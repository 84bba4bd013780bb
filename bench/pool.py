"""The benchmark's traffic: a seeded pool of distinct image batches.

`procedural_images` is a copy of the training data generator of
`repro.data.synthetic`, kept here so that a change to the program's data
module cannot change what the benchmark feeds it. The pool is made on the
device in one jitted call from the seed and cut into batches of the global
size (W workers x the per-worker batch), each placed where the launcher
places its batch: an uncommitted array on the default device.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A threefry key from any whole seed; equal to `jax.random.key(seed)`
    for 0 <= seed < 2**32, and distinct above it (where `key` truncates)."""
    seed %= 1 << 64
    data = np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(data, impl="threefry2x32")


def procedural_images(key, n, size=32, channels=3):
    """Images of a randomly-placed, randomly-oriented Gaussian blob with a
    color gradient. Values in [-1, 1]."""
    ks = jax.random.split(key, 5)
    cx = jax.random.uniform(ks[0], (n, 1, 1, 1), minval=0.25, maxval=0.75)
    cy = jax.random.uniform(ks[1], (n, 1, 1, 1), minval=0.25, maxval=0.75)
    sig = jax.random.uniform(ks[2], (n, 1, 1, 1), minval=0.05, maxval=0.15)
    hue = jax.random.uniform(ks[3], (n, 1, 1, channels))
    yy, xx = jnp.meshgrid(jnp.linspace(0, 1, size), jnp.linspace(0, 1, size),
                          indexing="ij")
    grid_x = xx[None, :, :, None]
    grid_y = yy[None, :, :, None]
    blob = jnp.exp(-((grid_x - cx) ** 2 + (grid_y - cy) ** 2) / (2 * sig**2))
    phase = 2 * math.pi * (hue + jnp.arange(channels) / channels)
    color = 0.5 + 0.5 * jnp.sin(phase)
    img = blob * color + 0.1 * (grid_x + grid_y) - 0.5
    return jnp.clip(2 * img, -1, 1)


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _pool(key, n_batches, rows, size, channels):
    keys = jax.random.split(key, n_batches)
    stack = jax.vmap(lambda k: procedural_images(k, rows, size, channels))(
        keys)
    return tuple(stack[i] for i in range(n_batches))


def pool_key(seed: int):
    """The pool's own stream, apart from the trainer's key."""
    return jax.random.fold_in(seed_key(seed), 0x9001)


def make_pool(seed: int, n_batches: int, rows: int, size: int,
              channels: int):
    """`n_batches` distinct batches of `rows` images, as a list of
    {"real": (rows, size, size, channels)} device arrays."""
    return [{"real": x}
            for x in _pool(pool_key(seed), n_batches, rows, size, channels)]
