"""The DCGAN family: the BN-free WGAN DCGAN of `repro.models.gan`.

What the harness needs to know of this architecture, and nothing else: the
program's model config, the data, the plain reference of the field, the
model FLOPs and the parameter sizes, and the smoke size of the CPU tests.
The harness finds this module by the `"family"` key of a configuration file
(`bench/configs/<config>.json`) and hands each function that file's object;
this family reads its `gan_config`.

Data: images of a randomly placed, randomly oriented Gaussian blob with a
color gradient, in [-1, 1], the batch `{"real": (rows, H, W, C)}`.

Reference: the WGAN field of the DCGAN, [grad of L_G = -E D(G(z)) in the
generator, disc_grad_mult x grad of L_D = -E D(x) + E D(G(z)) in the
critic], in plain `jax.numpy` and `jax.lax` at the matmul precision the
configuration states; the noise is the latent z of each row. The field
keeps no state of its own.

Model FLOPs of one field evaluation (`gan_field_fn`), counted as the two
gradients the field needs, with every product that lands on padding or on
the holes of a strided transposed convolution left out:

  L_G:  G forward; D forward on the fakes; D input-gradients on the fakes
        (every layer, down to the image); G weight-gradients and G
        input-gradients (every layer but the first, whose input is z).
  L_D:  D forward on the reals; D input-gradients on the reals (every
        layer but the first); D weight-gradients on the reals and on the
        fakes.

Each product the field needs is counted once. L_D's loss on the fakes is
L_G's negated, so its input-gradients on the fakes are L_G's with the sign
flipped: they are counted on L_G's side alone, as are the forward passes
that L_D repeats (G on z, D on the fakes). Elementwise operations, the
optimizer, error feedback, the quantizer and the exchange do not count. One
FLOP is one multiply or one add: a multiply-accumulate is two.
"""
from __future__ import annotations

import copy
import math

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.gan import GANConfig

import pool
from reference import operand

KERNEL = 4   # the repo's conv helpers fix 4x4 kernels, stride 2, SAME
STRIDE = 2
DN = ("NHWC", "HWIO", "NHWC")
SMOKE = {"image_size": 8, "latent_dim": 16, "base_width": 8}


def program_config(config: dict) -> GANConfig:
    """The `GANConfig` a configuration file describes."""
    return GANConfig(**config["gan_config"])


def smoke(config: dict) -> dict:
    """A copy of `config` at the CPU tests' size: 8x8 images, latent 16,
    base width 8."""
    out = copy.deepcopy(config)
    out["gan_config"].update(SMOKE)
    return out


# --------------------------------------------------------------------------- #
# data
# --------------------------------------------------------------------------- #
def procedural_images(key, n, size=32, channels=3):
    """Images of a randomly-placed, randomly-oriented Gaussian blob with a
    color gradient. Values in [-1, 1]. A copy of the training data
    generator of `repro.data.synthetic`, kept here so that a change to the
    program's data module cannot change what the benchmark feeds it."""
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


def _batch(key, rows, size, channels):
    return {"real": procedural_images(key, rows, size, channels)}


def make_pool(config: dict, seed: int, n_batches: int, rows: int):
    """`n_batches` distinct batches of `rows` images, as a list of
    {"real": (rows, size, size, channels)} device arrays."""
    gc = config["gan_config"]
    return pool.make_pool(seed, n_batches, rows, _batch, gc["image_size"],
                          gc["channels"])


# --------------------------------------------------------------------------- #
# reference
# --------------------------------------------------------------------------- #
def init_params(config: dict, key):
    """DCGAN weights from the key: conv kernels N(0, 0.02), fc weights
    N(0, 1/fan_in), biases 0, one split of the key per layer."""
    gc = config["gan_config"]
    bw, s0, ch, lat = (gc["base_width"], gc["image_size"] // 8,
                       gc["channels"], gc["latent_dim"])
    ks = jax.random.split(key, 10)

    def conv(k, cin, cout):
        return {"w": jax.random.normal(k, (4, 4, cin, cout)) * 0.02,
                "b": jnp.zeros((cout,))}

    def fc(k, din, dout):
        return {"w": jax.random.normal(k, (din, dout), jnp.float32)
                * (1.0 / math.sqrt(din)), "b": jnp.zeros((dout,))}

    return {"gen": {"fc": fc(ks[0], lat, s0 * s0 * bw * 4),
                    "c1": conv(ks[1], bw * 4, bw * 2),
                    "c2": conv(ks[2], bw * 2, bw),
                    "c3": conv(ks[3], bw, ch)},
            "disc": {"c1": conv(ks[4], ch, bw),
                     "c2": conv(ks[5], bw, bw * 2),
                     "c3": conv(ks[6], bw * 2, bw * 4),
                     "fc": fc(ks[7], s0 * s0 * bw * 4, 1)}}


def init_field_state(config: dict, key) -> dict:
    """The field keeps no state."""
    return {}


def draw(config: dict, key, rows: int):
    """The field's noise for `rows` rows: the latent z."""
    return jax.random.normal(key, (rows, config["gan_config"]["latent_dim"]))


def generate(gen, gc, z, prec, control):
    bw, s0 = gc["base_width"], gc["image_size"] // 8
    p = jax.tree.map(lambda x: operand(x, control), gen)
    x = jnp.dot(operand(z, control), p["fc"]["w"], precision=prec)
    x = jax.nn.relu(x + p["fc"]["b"]).reshape(-1, s0, s0, bw * 4)
    for name, act in (("c1", jax.nn.relu), ("c2", jax.nn.relu),
                      ("c3", jnp.tanh)):
        x = lax.conv_transpose(operand(x, control), p[name]["w"], (2, 2),
                               "SAME", dimension_numbers=DN, precision=prec)
        x = act(x + p[name]["b"])
    return x


def discriminate(disc, gc, x, prec, control):
    p = jax.tree.map(lambda a: operand(a, control), disc)
    h = x
    for name in ("c1", "c2", "c3"):
        h = lax.conv_general_dilated(operand(h, control), p[name]["w"],
                                     (2, 2), "SAME", dimension_numbers=DN,
                                     precision=prec)
        h = jax.nn.leaky_relu(h + p[name]["b"], 0.2)
    h = h.reshape(h.shape[0], -1)
    return (jnp.dot(operand(h, control), p["fc"]["w"], precision=prec)
            + p["fc"]["b"])[:, 0]


def field(params, state, config, batch, z, prec, control):
    """(field, loss = L_D + L_G, mean |D(real)|, state)."""
    gc = config["gan_config"]
    real, mult = batch["real"], gc["disc_grad_mult"]

    def loss_g(gen):
        d = discriminate(params["disc"], gc, generate(gen, gc, z, prec,
                                                      control), prec, control)
        return -jnp.mean(d.astype(jnp.float32))

    def loss_d(disc):
        fake = lax.stop_gradient(generate(params["gen"], gc, z, prec,
                                          control))
        d_real = discriminate(disc, gc, real, prec, control).astype(
            jnp.float32)
        d_fake = discriminate(disc, gc, fake, prec, control).astype(
            jnp.float32)
        return -jnp.mean(d_real) + jnp.mean(d_fake), jnp.mean(
            jnp.abs(d_real))

    lg, g_gen = jax.value_and_grad(loss_g)(params["gen"])
    (ld, scale), g_disc = jax.value_and_grad(loss_d, has_aux=True)(
        params["disc"])
    grads = {"gen": g_gen,
             "disc": jax.tree.map(lambda x: mult * x, g_disc)}
    return grads, ld + lg, scale, state


# --------------------------------------------------------------------------- #
# model FLOPs and parameter sizes
# --------------------------------------------------------------------------- #
def conv_taps(h_in: int, k: int = KERNEL, s: int = STRIDE) -> int:
    """Products along one spatial axis of a stride-`s` SAME convolution of
    an axis of length `h_in`, summed over output positions, counting only
    taps that land inside the input."""
    out = -(-h_in // s)
    lo = max((out - 1) * s + k - h_in, 0) // 2
    return sum(1 for o in range(out) for t in range(k)
               if 0 <= o * s + t - lo < h_in)


def conv_t_taps(h_in: int, k: int = KERNEL, s: int = STRIDE) -> int:
    """The same for a stride-`s` SAME transposed convolution (output
    `h_in * s`), which `lax.conv_transpose` runs over the input dilated by
    `s` and padded by `pad_a` in front: taps on a hole or on padding do
    not count."""
    pad_len = k + s - 2
    pad_a = k - 1 if s > k - 1 else -(-pad_len // 2)
    dilated = (h_in - 1) * s + 1
    return sum(1 for o in range(h_in * s) for t in range(k)
               if 0 <= o + t - pad_a < dilated and (o + t - pad_a) % s == 0)


def layer_macs(config: dict):
    """Multiply-accumulates per image of each layer, in forward order:
    (generator [fc, c1, c2, c3], critic [c1, c2, c3, fc])."""
    gc = config["gan_config"]
    bw, img, ch, lat = (gc["base_width"], gc["image_size"], gc["channels"],
                        gc["latent_dim"])
    s0 = img // 8
    gen = [lat * s0 * s0 * bw * 4]
    h, cin = s0, bw * 4
    for cout in (bw * 2, bw, ch):
        gen.append(conv_t_taps(h) ** 2 * cin * cout)
        h, cin = h * 2, cout
    disc = []
    h, cin = img, ch
    for cout in (bw, bw * 2, bw * 4):
        disc.append(conv_taps(h) ** 2 * cin * cout)
        h, cin = -(-h // 2), cout
    disc.append(s0 * s0 * bw * 4)
    return gen, disc


def field_flops_per_image(config: dict) -> int:
    """Model FLOPs of the WGAN field for one image (see the module doc)."""
    gen, disc = layer_macs(config)
    g_fwd, d_fwd = sum(gen), sum(disc)
    g_bwd = sum(gen) + sum(gen[1:])            # dW all, dX all but first
    d_dx_all = sum(disc)
    d_dw, d_dx_inner = sum(disc), sum(disc[1:])  # dW all; dX all but first
    macs = (g_fwd + d_fwd + d_dx_all + g_bwd     # L_G
            + d_fwd + d_dx_inner + 2 * d_dw)     # L_D: reals; dW on fakes
    return 2 * macs


def step_flops(config: dict, batch_per_worker: int, workers: int) -> int:
    """Model FLOPs of one training step over all workers."""
    return field_flops_per_image(config) * batch_per_worker * workers


def param_sizes(config: dict):
    """Element counts of the parameter leaves in `jax.tree.flatten` order
    (dicts by sorted key: disc before gen; within a layer b before w)."""
    gc = config["gan_config"]
    bw, s0, ch, lat = (gc["base_width"], gc["image_size"] // 8,
                       gc["channels"], gc["latent_dim"])
    kk = KERNEL * KERNEL
    disc = [("c1", bw, kk * ch * bw), ("c2", bw * 2, kk * bw * bw * 2),
            ("c3", bw * 4, kk * bw * 2 * bw * 4), ("fc", 1, s0 * s0 * bw * 4)]
    gen = [("c1", bw * 2, kk * bw * 4 * bw * 2), ("c2", bw, kk * bw * 2 * bw),
           ("c3", ch, kk * bw * ch), ("fc", s0 * s0 * bw * 4,
                                      lat * s0 * s0 * bw * 4)]
    return [n for _, b, w in disc + gen for n in (b, w)]


def n_params(config: dict) -> int:
    return sum(param_sizes(config))
