"""The DCGAN family's model-FLOP count and the kernel-byte functions
against hand counts, against XLA's count of the compiled field, and pinned;
the bucket layout against the program's planner."""
import math

import jax
import jax.numpy as jnp
import pytest
from jax import lax

import benchkit

import flops

CONFIG, DCGAN = benchkit.config("dcgan32")
SMOKE = DCGAN.smoke(CONFIG)


def _valid_products(x_shape, transpose):
    """Products that touch input and weight, counted by convolving ones:
    each output sums its valid taps."""
    x = jnp.ones(x_shape)
    w = jnp.ones((4, 4, 1, 1))
    dn = ("NHWC", "HWIO", "NHWC")
    if transpose:
        y = lax.conv_transpose(x, w, (2, 2), "SAME", dimension_numbers=dn)
    else:
        y = lax.conv_general_dilated(x, w, (2, 2), "SAME",
                                     dimension_numbers=dn)
    return int(y.sum())


@pytest.mark.parametrize("h", [2, 4, 8, 16, 32])
def test_taps_by_convolving_ones(h):
    assert DCGAN.conv_taps(h) ** 2 == _valid_products((1, h, h, 1), False)
    assert DCGAN.conv_t_taps(h) ** 2 == _valid_products((1, h, h, 1), True)


def test_smoke_hand_count():
    # 8x8x3 images, latent 16, base width 8, s0 = 1.
    # taps per axis, counted by hand: conv over 8 -> 14, over 4 -> 6,
    # over 2 -> 2; conv_transpose of 1 -> 2, of 2 -> 6, of 4 -> 14.
    assert [DCGAN.conv_taps(h) for h in (8, 4, 2)] == [14, 6, 2]
    assert [DCGAN.conv_t_taps(h) for h in (1, 2, 4)] == [2, 6, 14]
    gen, disc = DCGAN.layer_macs(SMOKE)
    assert gen == [16 * 32, 2**2 * 32 * 16, 6**2 * 16 * 8, 14**2 * 8 * 3]
    assert disc == [14**2 * 3 * 8, 6**2 * 8 * 16, 2**2 * 16 * 32, 32]
    g, d = sum(gen), sum(disc)
    # L_G: G fwd, D fwd, D dX (all), G dW + dX (not the first layer);
    # L_D: D fwd on reals, dW + dX (not the first) on reals, dW on fakes
    macs = (g + d + d + g + (g - gen[0])
            + d + (d + d - disc[0]) + d)
    assert DCGAN.field_flops_per_image(SMOKE) == 2 * macs
    assert DCGAN.step_flops(SMOKE, 8, 4) == 2 * macs * 32


# The counts as they stood when the model-specific code moved into the
# families: each cell's step at its own batch, and the parameter leaves.
PINNED = {
    "dcgan32": (64, 16_785_342_464, 1_849_988,
                [64, 3072, 128, 131072, 256, 524288, 1, 4096,
                 128, 524288, 64, 131072, 3, 3072, 4096, 524288]),
    "dcgan32-dim128": (512, 522_840_965_120, 6_321_412,
                       [128, 6144, 256, 524288, 512, 2097152, 1, 8192,
                        256, 2097152, 128, 524288, 3, 6144, 8192,
                        1048576]),
}


@pytest.mark.parametrize("config", sorted(PINNED))
def test_counts_pinned(config):
    cfg, fam = benchkit.config(config)
    batch, step, n, sizes = PINNED[config]
    assert fam.step_flops(cfg, batch, 1) == step
    assert fam.param_sizes(cfg) == sizes
    assert fam.n_params(cfg) == n == cfg["n_params"]


def _single_backward_field(cfg):
    """The WGAN field of `gan_field_fn` with the critic's backward pass on
    the fakes run once: one VJP of D on the fakes with cotangent +1/B gives
    L_D's weight-gradients there, and its input cotangent, negated, is
    L_G's, fed to the generator's VJP."""
    from repro.models.gan import dcgan_discriminate, dcgan_generate

    def field(params, batch, rng):
        real = batch["real"]
        z = jax.random.normal(rng, (real.shape[0], cfg.latent_dim))
        fake, gen_vjp = jax.vjp(lambda g: dcgan_generate(g, cfg, z),
                                params["gen"])
        d_fake, fake_vjp = jax.vjp(
            lambda d, x: dcgan_discriminate(d, cfg, x), params["disc"], fake)
        d_real, real_vjp = jax.vjp(
            lambda d: dcgan_discriminate(d, cfg, real), params["disc"])
        gd_fake, gx_fake = fake_vjp(jnp.full_like(d_fake, 1 / d_fake.size))
        (gd_real,) = real_vjp(jnp.full_like(d_real, -1 / d_real.size))
        (g_gen,) = gen_vjp(-gx_fake)
        g_disc = jax.tree.map(lambda r, f: cfg.disc_grad_mult * (r + f),
                              gd_real, gd_fake)
        lg = -jnp.mean(d_fake)
        ld = -jnp.mean(d_real) + jnp.mean(d_fake)
        return {"gen": g_gen, "disc": g_disc}, {"loss": ld + lg,
                                                "loss_g": lg, "loss_d": ld}

    return field


def _dcgan32():
    return CONFIG, DCGAN.program_config(CONFIG)


def _compiled_flops(field, cfg, batch):
    from repro.models.gan import init

    params = jax.eval_shape(lambda k: init(k, cfg), jax.random.key(0))
    real = jax.ShapeDtypeStruct(
        (batch, cfg.image_size, cfg.image_size, cfg.channels), jnp.float32)
    compiled = jax.jit(lambda p, r, k: field(p, {"real": r}, k)).lower(
        params, real, jax.random.key(0)).compile()
    return compiled.cost_analysis()["flops"]


def test_field_against_compiled_count():
    """The model count leaves out only elementwise work: within 2% of
    XLA's count of a compiled dcgan32 field at batch 64 that runs each
    product once, and within 2% of the program's own field, which runs
    each product once too."""
    from repro.models.gan import gan_field_fn

    config, cfg = _dcgan32()
    model = DCGAN.step_flops(config, 64, 1)
    for field in (_single_backward_field(cfg), gan_field_fn(cfg)):
        compiled = _compiled_flops(field, cfg, 64)
        assert model <= compiled and (compiled - model) / compiled < 0.02


def test_single_backward_field_is_the_programs():
    """The field the count is held against computes the program's: the
    same losses and gradients on seeded dcgan32 weights, to float32
    rounding."""
    from repro.models.gan import gan_field_fn, init

    _, cfg = _dcgan32()
    params = init(jax.random.key(3), cfg)
    batch = {"real": jax.random.uniform(jax.random.key(4), (16, 32, 32, 3),
                                        minval=-1.0, maxval=1.0)}
    rng = jax.random.key(5)
    g_ours, m_ours = jax.jit(_single_backward_field(cfg))(params, batch, rng)
    g_prog, m_prog = jax.jit(gan_field_fn(cfg))(params, batch, rng)
    for k in ("loss", "loss_g", "loss_d"):
        assert abs(float(m_ours[k]) - float(m_prog[k])) <= \
            1e-5 * abs(float(m_prog[k]))
    ours, prog = jax.tree.leaves(g_ours), jax.tree.leaves(g_prog)
    norms = [float(jnp.linalg.norm(p)) for p in prog]
    median = sorted(norms)[len(norms) // 2]
    assert median > 0
    for a, b, n in zip(ours, prog, norms):
        assert a.shape == b.shape
        assert float(jnp.linalg.norm(a - b)) <= 1e-5 * max(n, median)


PEAKS = {"hbm_gbps": 819.0, "vmem_read_gbps": 18432.0,
         "vmem_write_gbps": 6144.0}


def test_kernel_bytes_hand_count():
    # dcgan32's first bucket on one chip: 648 rows of 1024. Reads g, e and
    # the uniforms (4 B each), writes int8 codes, one f32 scale per row and
    # the f32 residual.
    n = 648 * 1024
    assert flops.quantize_kernel_arrays(648, 1024) == (
        [4 * n, 4 * n, 4 * n], [n, 648 * 4, 4 * n])
    # every array in VMEM, as XLA placed dcgan32's buckets: the stores
    # bound it
    t, bound = flops.least_seconds(648, 1024, [1, 1, 1], [1, 1, 1], PEAKS)
    assert bound == "vmem_store"
    assert t == pytest.approx((5 * n + 648 * 4) / 6144e9)
    # every array in HBM: HBM bounds it
    t, bound = flops.least_seconds(648, 1024, [0, 0, 0], [0, 0, 0], PEAKS)
    assert bound == "hbm"
    assert t == pytest.approx((n * 17 + 648 * 4) / 819e9)


@pytest.mark.parametrize("workers", [1, 4])
@pytest.mark.parametrize("config", ["dcgan32", "dcgan32-dim128"])
def test_buckets_match_the_planner(config, workers):
    from repro.comm import build_layout
    from repro.models import build

    cfg, fam = benchkit.config(config)
    shapes = jax.tree.map(
        lambda x: tuple(x.shape),
        jax.eval_shape(build(fam.program_config(cfg)).init,
                       jax.random.key(0)))
    layout = build_layout(shapes, None, workers)
    ours = flops.bucket_layout(fam.param_sizes(cfg), workers)
    assert [b.size for b in layout.buckets] == [s for s, _ in ours]
    assert [[s.index for s in b.slots] for b in layout.buckets] == \
        [m for _, m in ours]
    if config == "dcgan32":
        # rows of 1024 of each kernel call, as seen in the step compiled for
        # a TPU v5e: the whole bucket on one chip, each owner's quarter of
        # it in two_phase on four
        assert [s // workers // 1024 for s, _ in ours] == (
            [648, 648, 512] if workers == 1 else [162, 162, 128])
    assert sum(math.prod(s) for s in jax.tree.leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple))) == \
        fam.n_params(cfg)
