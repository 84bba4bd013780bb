"""The MLP GAN family, for the test that adds a family as files alone: the
generator and critic that `repro.models.gan` builds for vector data
(`GANConfig(image_size=0)`, its `mlp_*` functions), on points in the plane.

Data: a ring of Gaussians, `config["data"]`'s `modes` centres on a circle
of `radius`, each point drawn about a uniformly chosen centre with
standard deviation `std`; the batch `{"real": (rows, 2)}`.

Reference: the WGAN field, [grad of L_G = -E D(G(z)) in the generator,
disc_grad_mult x grad of L_D = -E D(x) + E D(G(z)) in the critic], with
three linear layers on each side (ReLU in the generator, leaky ReLU 0.2 in
the critic), in plain `jax.numpy`; no state of its own.

Model FLOPs: the products of the two gradients, each counted once, as the
DCGAN family counts them: L_G takes G forward, D forward and input-gradients
on the fakes, G's weight-gradients and its input-gradients but the first
layer's; L_D takes D forward on the reals, its input-gradients there but
the first layer's, and D's weight-gradients on reals and fakes.
"""
from __future__ import annotations

import copy
import math

import jax
import jax.numpy as jnp

from repro.models.gan import GANConfig

import pool
from reference import operand

SMOKE = {"latent_dim": 8, "hidden": 32}


def program_config(config: dict) -> GANConfig:
    return GANConfig(**config["gan_config"])


def smoke(config: dict) -> dict:
    out = copy.deepcopy(config)
    out["gan_config"].update(SMOKE)
    return out


def _ring(key, rows, modes, radius, std):
    kc, kn = jax.random.split(key)
    angle = 2 * math.pi * jax.random.randint(kc, (rows,), 0, modes) / modes
    centres = radius * jnp.stack([jnp.cos(angle), jnp.sin(angle)], axis=1)
    return {"real": centres + std * jax.random.normal(kn, (rows, 2))}


def make_pool(config: dict, seed: int, n_batches: int, rows: int):
    d = config["data"]
    return pool.make_pool(seed, n_batches, rows, _ring, d["modes"],
                          d["radius"], d["std"])


def init_params(config: dict, key):
    """Linear weights N(0, 1/fan_in), biases 0, one split of the key per
    layer."""
    gc = config["gan_config"]
    h, lat, d = gc["hidden"], gc["latent_dim"], gc["data_dim"]
    ks = jax.random.split(key, 6)

    def fc(k, din, dout):
        return {"w": jax.random.normal(k, (din, dout), jnp.float32)
                * (1.0 / math.sqrt(din)), "b": jnp.zeros((dout,))}

    return {"gen": {"l1": fc(ks[0], lat, h), "l2": fc(ks[1], h, h),
                    "l3": fc(ks[2], h, d)},
            "disc": {"l1": fc(ks[3], d, h), "l2": fc(ks[4], h, h),
                     "l3": fc(ks[5], h, 1)}}


def init_field_state(config: dict, key) -> dict:
    return {}


def draw(config: dict, key, rows: int):
    return jax.random.normal(key, (rows, config["gan_config"]["latent_dim"]))


def _mlp(params, x, act, prec, control):
    p = jax.tree.map(lambda a: operand(a, control), params)
    for name in ("l1", "l2"):
        x = act(jnp.dot(operand(x, control), p[name]["w"], precision=prec)
                + p[name]["b"])
    return jnp.dot(operand(x, control), p["l3"]["w"],
                   precision=prec) + p["l3"]["b"]


def _generate(gen, z, prec, control):
    return _mlp(gen, z, jax.nn.relu, prec, control)


def _discriminate(disc, x, prec, control):
    return _mlp(disc, x, lambda a: jax.nn.leaky_relu(a, 0.2), prec,
                control)[:, 0]


def field(params, state, config, batch, z, prec, control):
    """(field, loss = L_D + L_G, mean |D(real)|, state)."""
    real, mult = batch["real"], config["gan_config"]["disc_grad_mult"]

    def loss_g(gen):
        d = _discriminate(params["disc"], _generate(gen, z, prec, control),
                          prec, control)
        return -jnp.mean(d.astype(jnp.float32))

    def loss_d(disc):
        fake = jax.lax.stop_gradient(_generate(params["gen"], z, prec,
                                               control))
        d_real = _discriminate(disc, real, prec, control).astype(jnp.float32)
        d_fake = _discriminate(disc, fake, prec, control).astype(jnp.float32)
        return -jnp.mean(d_real) + jnp.mean(d_fake), jnp.mean(
            jnp.abs(d_real))

    lg, g_gen = jax.value_and_grad(loss_g)(params["gen"])
    (ld, scale), g_disc = jax.value_and_grad(loss_d, has_aux=True)(
        params["disc"])
    return ({"gen": g_gen, "disc": jax.tree.map(lambda x: mult * x, g_disc)},
            ld + lg, scale, state)


def layer_macs(config: dict):
    gc = config["gan_config"]
    h, lat, d = gc["hidden"], gc["latent_dim"], gc["data_dim"]
    return [lat * h, h * h, h * d], [d * h, h * h, h]


def step_flops(config: dict, batch_per_worker: int, workers: int) -> int:
    gen, disc = layer_macs(config)
    g, d = sum(gen), sum(disc)
    macs = (g + d + d + g + (g - gen[0])          # L_G
            + d + (d - disc[0]) + 2 * d)          # L_D
    return 2 * macs * batch_per_worker * workers


def param_sizes(config: dict):
    """Leaf sizes in `jax.tree.flatten` order: disc before gen, b before
    w."""
    gc = config["gan_config"]
    h, lat, d = gc["hidden"], gc["latent_dim"], gc["data_dim"]
    disc = [(h, d * h), (h, h * h), (1, h)]
    gen = [(h, lat * h), (h, h * h), (d, h * d)]
    return [n for b, w in disc + gen for n in (b, w)]


def n_params(config: dict) -> int:
    return sum(param_sizes(config))
