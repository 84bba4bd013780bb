"""GANs — the paper's own experimental architectures.

Two families:
  * DCGAN-style conv generator/discriminator for image data (the paper's
    CIFAR10/CelebA setup, §4), built on lax.conv_general_dilated.
  * MLP generator/discriminator for low-dimensional synthetic data
    (2-D Gaussian mixtures) — used by the quickstart + convergence bench.

Loss: WGAN (paper Eq. 3):
    L_D = -E_x[D(x)] + E_z[D(G(z))]       L_G = -E_z[D(G(z))]
The min-max field (paper Eq. 10) is F(w) = [∇θ L_G, ∇φ L_D] — that is what
DQGAN exchanges/averages across workers.

BigGAN (`models/biggan.py`) takes the hinge loss instead, on
class-labelled batches, with the spectral-norm vectors as the field's state
(`hinge_field_fn`).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from . import biggan
from .layers import linear, linear_init


@dataclass(frozen=True)
class GANConfig:
    name: str = "dcgan32"
    arch_type: str = "gan"
    image_size: int = 32          # 0 -> vector data (MLP GAN)
    channels: int = 3
    latent_dim: int = 128
    base_width: int = 64
    data_dim: int = 2             # for MLP GAN
    hidden: int = 128
    weight_clip: float = 0.1      # WGAN Lipschitz via clipping
    # critic-to-generator learning-rate ratio; the simultaneous-update
    # equivalent of WGAN's n_critic=5 (scales the disc part of the field)
    disc_grad_mult: float = 5.0

    @property
    def is_image(self) -> bool:
        return self.image_size > 0

    def reduced(self) -> "GANConfig":
        return GANConfig(name=self.name + "-smoke", image_size=8, channels=1,
                         latent_dim=16, base_width=8)


# --------------------------------------------------------------------------- #
# conv helpers (NHWC)
# --------------------------------------------------------------------------- #
def conv_init(key, kh, kw, cin, cout):
    fan = kh * kw * cin
    return {"w": jax.random.normal(key, (kh, kw, cin, cout)) * 0.02,
            "b": jnp.zeros((cout,))}


def conv(p, x, stride=2):
    y = jax.lax.conv_general_dilated(
        x, p["w"], (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"]


def conv_t(p, x, stride=2):
    y = jax.lax.conv_transpose(
        x, p["w"], (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"]


def _bn_free_act(x):  # DCGAN without batchnorm (WGAN-friendly): leaky relu
    return jax.nn.leaky_relu(x, 0.2)


# --------------------------------------------------------------------------- #
# DCGAN
# --------------------------------------------------------------------------- #
def dcgan_init(key, cfg: GANConfig):
    bw = cfg.base_width
    s0 = cfg.image_size // 8  # three stride-2 upsamples
    ks = jax.random.split(key, 10)
    gen = {
        "fc": linear_init(ks[0], cfg.latent_dim, s0 * s0 * bw * 4, True),
        "c1": conv_init(ks[1], 4, 4, bw * 4, bw * 2),
        "c2": conv_init(ks[2], 4, 4, bw * 2, bw),
        "c3": conv_init(ks[3], 4, 4, bw, cfg.channels),
    }
    disc = {
        "c1": conv_init(ks[4], 4, 4, cfg.channels, bw),
        "c2": conv_init(ks[5], 4, 4, bw, bw * 2),
        "c3": conv_init(ks[6], 4, 4, bw * 2, bw * 4),
        "fc": linear_init(ks[7], s0 * s0 * bw * 4, 1, True),
    }
    return {"gen": gen, "disc": disc}


def dcgan_generate(gen, cfg: GANConfig, z):
    bw = cfg.base_width
    s0 = cfg.image_size // 8
    x = jax.nn.relu(linear(gen["fc"], z)).reshape(-1, s0, s0, bw * 4)
    x = jax.nn.relu(conv_t(gen["c1"], x))
    x = jax.nn.relu(conv_t(gen["c2"], x))
    return jnp.tanh(conv_t(gen["c3"], x))


def dcgan_discriminate(disc, cfg: GANConfig, x):
    h = _bn_free_act(conv(disc["c1"], x))
    h = _bn_free_act(conv(disc["c2"], h))
    h = _bn_free_act(conv(disc["c3"], h))
    return linear(disc["fc"], h.reshape(h.shape[0], -1))[:, 0]


# --------------------------------------------------------------------------- #
# MLP GAN (synthetic 2-D data)
# --------------------------------------------------------------------------- #
def mlp_gan_init(key, cfg: GANConfig):
    ks = jax.random.split(key, 6)
    h = cfg.hidden
    gen = {
        "l1": linear_init(ks[0], cfg.latent_dim, h, True),
        "l2": linear_init(ks[1], h, h, True),
        "l3": linear_init(ks[2], h, cfg.data_dim, True),
    }
    disc = {
        "l1": linear_init(ks[3], cfg.data_dim, h, True),
        "l2": linear_init(ks[4], h, h, True),
        "l3": linear_init(ks[5], h, 1, True),
    }
    return {"gen": gen, "disc": disc}


def mlp_generate(gen, cfg, z):
    h = jax.nn.relu(linear(gen["l1"], z))
    h = jax.nn.relu(linear(gen["l2"], h))
    return linear(gen["l3"], h)


def mlp_discriminate(disc, cfg, x):
    h = jax.nn.leaky_relu(linear(disc["l1"], x), 0.2)
    h = jax.nn.leaky_relu(linear(disc["l2"], h), 0.2)
    return linear(disc["l3"], h)[:, 0]


# --------------------------------------------------------------------------- #
# the min-max field (what DQGAN transports)
# --------------------------------------------------------------------------- #
def init(key, cfg, max_seq: int = 0):
    del max_seq
    if isinstance(cfg, biggan.BigGANConfig):
        return biggan.init(key, cfg)
    return (dcgan_init if cfg.is_image else mlp_gan_init)(key, cfg)


def gan_field_fn(cfg):
    """Returns field_fn(params, batch, rng) -> (grads, metrics) for DQGAN.
    batch: {"real": real samples}. A BigGAN config gets the stateful
    hinge field of `hinge_field_fn` instead.

    The critic's backward pass on the fakes runs once, shared by both
    losses: its VJP seeded with +1/B gives L_D's weight-gradients there, and
    its input cotangent, negated, is L_G's, fed to the generator's VJP. The
    reals take weight-gradients only."""
    if isinstance(cfg, biggan.BigGANConfig):
        return hinge_field_fn(cfg)
    gen_f = dcgan_generate if cfg.is_image else mlp_generate
    disc_f = dcgan_discriminate if cfg.is_image else mlp_discriminate

    def field_fn(params, batch, rng):
        real = batch["real"]
        z = jax.random.normal(rng, (real.shape[0], cfg.latent_dim))
        fake, gen_vjp = jax.vjp(lambda g: gen_f(g, cfg, z), params["gen"])
        d_fake, fake_vjp = jax.vjp(lambda d, x: disc_f(d, cfg, x),
                                   params["disc"], fake)
        d_real, real_vjp = jax.vjp(lambda d: disc_f(d, cfg, real),
                                   params["disc"])
        gd_fake, gx_fake = fake_vjp(jnp.full_like(d_fake, 1 / d_fake.size))
        (gd_real,) = real_vjp(jnp.full_like(d_real, -1 / d_real.size))
        (g_gen,) = gen_vjp(-gx_fake)
        g_disc = jax.tree.map(lambda r, f: cfg.disc_grad_mult * (r + f),
                              gd_real, gd_fake)
        lg = -jnp.mean(d_fake)
        ld = -jnp.mean(d_real) + jnp.mean(d_fake)
        return ({"gen": g_gen, "disc": g_disc},
                {"loss": ld + lg, "loss_g": lg, "loss_d": ld})

    return field_fn


def hinge_field_fn(cfg: biggan.BigGANConfig):
    """The BigGAN hinge field, a stateful field for DQGAN:
    field_fn(params, batch, rng, sn_state) -> (grads, metrics, sn_state),
    and field_fn.init_state(params) -> the spectral-norm vectors u.
    batch: {"real": (B, H, W, 3), "label": (B,)}; the fakes' z and classes
    are drawn from rng (`biggan.draw`).

        L_D = E relu(1 - D(x, y)) + E relu(1 + D(G(z, y_f), y_f))
        L_G = -E D(G(z, y_f), y_f)

    F = [grad L_G in G, disc_grad_mult x grad L_D in D]. One power
    iteration per weight normalizes the parameters, shared by the reals,
    the fakes and both losses; the field's cotangent on the normalized
    parameters is pulled back through it once. D's backward pass on the
    fakes runs twice, with the two losses' cotangents there: L_D's
    weight-gradients take 1[1 + D > 0] / B, L_G's input-gradients -1 / B.
    The reals take weight-gradients only, under 1[1 - D > 0] / B.
    Metrics add the share of rows whose hinge is active on each side."""
    eps = cfg.SN_eps

    def field_fn(params, batch, rng, sn_state):
        real, y_real = batch["real"], batch["label"]
        z, y_fake = biggan.draw(cfg, rng, real.shape[0])
        normed, sn_vjp, new_state = jax.vjp(
            lambda p: biggan.spectral_norm(p, sn_state, eps), params,
            has_aux=True)
        fake, gen_vjp = jax.vjp(
            lambda g: biggan.generate(g, cfg, z, y_fake), normed["gen"])
        d_fake, fake_vjp = jax.vjp(
            lambda d, x: biggan.discriminate(d, cfg, x, y_fake),
            normed["disc"], fake)
        d_real, real_vjp = jax.vjp(
            lambda d: biggan.discriminate(d, cfg, real, y_real),
            normed["disc"])
        act_real = (1.0 - d_real > 0).astype(d_real.dtype)
        act_fake = (1.0 + d_fake > 0).astype(d_fake.dtype)
        gd_fake, _ = fake_vjp(act_fake / d_fake.size)
        _, gx_fake = fake_vjp(jnp.full_like(d_fake, -1 / d_fake.size))
        (gd_real,) = real_vjp(-act_real / d_real.size)
        (g_gen,) = gen_vjp(gx_fake)
        g_disc = jax.tree.map(lambda r, f: cfg.disc_grad_mult * (r + f),
                              gd_real, gd_fake)
        (grads,) = sn_vjp({"gen": g_gen, "disc": g_disc})
        lg = -jnp.mean(d_fake)
        ld = (jnp.mean(jax.nn.relu(1.0 - d_real))
              + jnp.mean(jax.nn.relu(1.0 + d_fake)))
        return grads, {"loss": ld + lg, "loss_g": lg, "loss_d": ld,
                       "hinge_active_real": jnp.mean(act_real),
                       "hinge_active_fake": jnp.mean(act_fake)}, new_state

    field_fn.init_state = biggan.init_sn_state
    return field_fn


def clip_disc(params, cfg: GANConfig):
    """WGAN weight clipping (applied to the discriminator after a step)."""
    c = cfg.weight_clip
    return {
        "gen": params["gen"],
        "disc": jax.tree.map(lambda w: jnp.clip(w, -c, c), params["disc"]),
    }
