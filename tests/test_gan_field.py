"""The WGAN field against the two-gradient formulation it replaces, and the
compiled count that shows the critic's backward pass on the fakes runs
once."""
import jax
import jax.numpy as jnp
import pytest

from repro.models.gan import (
    GANConfig,
    dcgan_discriminate,
    dcgan_generate,
    gan_field_fn,
    init,
    mlp_discriminate,
    mlp_generate,
)

DCGAN = GANConfig(image_size=32, channels=3, latent_dim=16, base_width=8)
MLP = GANConfig(name="toy", image_size=0, latent_dim=8, hidden=32)


def _two_gradient_field(cfg):
    """The field as two `value_and_grad` calls, one per loss (paper Eq. 3):
    each runs the critic's backward pass on the fakes."""
    gen_f = dcgan_generate if cfg.is_image else mlp_generate
    disc_f = dcgan_discriminate if cfg.is_image else mlp_discriminate

    def loss_g(gen, disc, z):
        return -jnp.mean(disc_f(disc, cfg, gen_f(gen, cfg, z)))

    def loss_d(disc, gen, real, z):
        fake = jax.lax.stop_gradient(gen_f(gen, cfg, z))
        return (-jnp.mean(disc_f(disc, cfg, real))
                + jnp.mean(disc_f(disc, cfg, fake)))

    def field(params, batch, rng):
        real = batch["real"]
        z = jax.random.normal(rng, (real.shape[0], cfg.latent_dim))
        lg, g_gen = jax.value_and_grad(loss_g)(params["gen"], params["disc"],
                                               z)
        ld, g_disc = jax.value_and_grad(loss_d)(params["disc"], params["gen"],
                                                real, z)
        g_disc = jax.tree.map(lambda x: cfg.disc_grad_mult * x, g_disc)
        return {"gen": g_gen, "disc": g_disc}, {"loss": ld + lg,
                                                "loss_g": lg, "loss_d": ld}

    return field


def _real(cfg, key, batch):
    shape = ((batch, cfg.image_size, cfg.image_size, cfg.channels)
             if cfg.is_image else (batch, cfg.data_dim))
    return jax.random.uniform(key, shape, minval=-1.0, maxval=1.0)


@pytest.mark.parametrize("cfg", [DCGAN, MLP], ids=["dcgan", "mlp"])
def test_shared_field_matches_two_gradient_field(cfg):
    """Losses to 1e-5 relative; each gradient leaf to 1e-5 of the larger of
    its own norm and the median leaf's. At DCGAN's init the generator's
    leaves are ~1e-3 of the critic's, so the median is a generator leaf and
    the critic's leaves are held to their own float32 rounding."""
    params = init(jax.random.key(3), cfg)
    batch = {"real": _real(cfg, jax.random.key(4), 16)}
    rng = jax.random.key(5)
    g_new, m_new = jax.jit(gan_field_fn(cfg))(params, batch, rng)
    g_old, m_old = jax.jit(_two_gradient_field(cfg))(params, batch, rng)
    for k in ("loss", "loss_g", "loss_d"):
        assert abs(float(m_new[k]) - float(m_old[k])) <= \
            1e-5 * abs(float(m_old[k]))
    assert jax.tree.structure(g_new) == jax.tree.structure(g_old)
    new, old = jax.tree.leaves(g_new), jax.tree.leaves(g_old)
    norms = sorted(float(jnp.linalg.norm(x)) for x in old)
    median = norms[len(norms) // 2]
    assert median > 0
    for a, b in zip(new, old):
        assert a.shape == b.shape
        assert float(jnp.linalg.norm(a - b)) <= \
            1e-5 * max(float(jnp.linalg.norm(b)), median)


def _compiled_flops(field, cfg, batch):
    params = jax.eval_shape(lambda k: init(k, cfg), jax.random.key(0))
    real = jax.ShapeDtypeStruct(
        (batch, cfg.image_size, cfg.image_size, cfg.channels), jnp.float32)
    compiled = jax.jit(lambda p, r, k: field(p, {"real": r}, k)).lower(
        params, real, jax.random.key(0)).compile()
    return compiled.cost_analysis()["flops"]


def test_shared_field_drops_the_repeated_backward():
    """dcgan32 (base width 64) at batch 64, compiled on the CPU: the shared
    field counts 16,843,852,800 FLOPs against the two-gradient field's
    18,620,473,344 (x0.9046): the critic's data-gradient convolutions on
    the fakes run once."""
    cfg = GANConfig(image_size=32, channels=3, latent_dim=128, base_width=64)
    shared = _compiled_flops(gan_field_fn(cfg), cfg, 64)
    old = _compiled_flops(_two_gradient_field(cfg), cfg, 64)
    assert shared <= 0.92 * old
