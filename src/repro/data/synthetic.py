"""Synthetic data pipelines (no external datasets are available offline).

* LM token streams: a deterministic Zipf-distributed Markov-ish stream so
  the loss is learnable (next token correlates with the current one).
* 2-D Gaussian mixtures: the classic GAN mode-coverage benchmark.
* Procedural images: CIFAR-shaped structured images (colored oriented
  blobs) giving the DCGAN a non-trivial distribution; stands in for
  CIFAR10/CelebA (DESIGN.md §7).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


# --------------------------------------------------------------------------- #
# LM tokens
# --------------------------------------------------------------------------- #
def synthetic_lm_batch(key, batch, seq, vocab):
    """Correlated token stream: t_{i+1} = (a * t_i + noise) mod vocab with a
    few preferred successor offsets — learnable by a small LM."""
    k1, k2 = jax.random.split(key)
    start = jax.random.randint(k1, (batch, 1), 0, vocab)
    offsets = jnp.array([1, 7, 13, 29])
    chose = jax.random.randint(k2, (batch, seq), 0, len(offsets))
    steps = offsets[chose]
    toks = (start + jnp.cumsum(steps, axis=1)) % vocab
    tokens = jnp.concatenate([start, toks[:, :-1]], axis=1)
    targets = toks
    return {"tokens": tokens.astype(jnp.int32),
            "targets": targets.astype(jnp.int32)}


def lm_batch_iterator(seed, batch, seq, vocab, enc_shape=None):
    key = jax.random.key(seed)
    i = 0
    while True:
        k = jax.random.fold_in(key, i)
        b = synthetic_lm_batch(k, batch, seq, vocab)
        if enc_shape is not None:
            b["enc_embeds"] = 0.1 * jax.random.normal(
                jax.random.fold_in(k, 1), (batch,) + enc_shape
            )
        yield b
        i += 1


# --------------------------------------------------------------------------- #
# 2-D Gaussian mixture (GAN synthetic benchmark)
# --------------------------------------------------------------------------- #
def gaussian_mixture_sampler(n_modes=8, radius=2.0, std=0.05):
    angles = np.linspace(0, 2 * math.pi, n_modes, endpoint=False)
    centers = jnp.array(
        np.stack([radius * np.cos(angles), radius * np.sin(angles)], -1),
        jnp.float32,
    )

    def sample(key, n):
        k1, k2 = jax.random.split(key)
        idx = jax.random.randint(k1, (n,), 0, n_modes)
        noise = std * jax.random.normal(k2, (n, 2))
        return centers[idx] + noise

    return sample, centers


# --------------------------------------------------------------------------- #
# procedural images (CIFAR stand-in)
# --------------------------------------------------------------------------- #
def procedural_images(key, n, size=32, channels=3, hue=None):
    """Images of a randomly-placed, randomly-oriented Gaussian blob with a
    color gradient — structured enough that a GAN must learn position,
    orientation and color jointly. Values in [-1, 1]. `hue`, (n,) in
    [0, 1), fixes each image's color; drawn per channel when None."""
    ks = jax.random.split(key, 5)
    cx = jax.random.uniform(ks[0], (n, 1, 1, 1), minval=0.25, maxval=0.75)
    cy = jax.random.uniform(ks[1], (n, 1, 1, 1), minval=0.25, maxval=0.75)
    sig = jax.random.uniform(ks[2], (n, 1, 1, 1), minval=0.05, maxval=0.15)
    if hue is None:
        hue = jax.random.uniform(ks[3], (n, 1, 1, channels))
    else:
        hue = hue.reshape(n, 1, 1, 1)
    yy, xx = jnp.meshgrid(jnp.linspace(0, 1, size), jnp.linspace(0, 1, size),
                          indexing="ij")
    grid_x = xx[None, :, :, None]
    grid_y = yy[None, :, :, None]
    blob = jnp.exp(-((grid_x - cx) ** 2 + (grid_y - cy) ** 2) / (2 * sig**2))
    phase = 2 * math.pi * (hue + jnp.arange(channels) / channels)
    color = 0.5 + 0.5 * jnp.sin(phase)
    img = blob * color + 0.1 * (grid_x + grid_y) - 0.5
    return jnp.clip(2 * img, -1, 1)


def labelled_images(key, n, size, channels, n_classes):
    """(images, labels) for a class-conditional GAN: labels uniform over
    `n_classes`, each image's color set by its label."""
    kl, ki = jax.random.split(key)
    labels = jax.random.randint(kl, (n,), 0, n_classes)
    return (procedural_images(ki, n, size, channels,
                              hue=labels / n_classes), labels)
