"""Shared harness for the paper's experimental comparison (§4):
DQGAN vs CPOAdam vs CPOAdam-GQ on synthetic data, with the paper's metric
shape (quality-vs-epoch curves) reproduced via:

  * mode coverage + high-quality-sample fraction on a 2-D Gaussian mixture
  * "synthetic FID": Fréchet distance between real/fake feature statistics
    in a fixed random projection feature space (the offline stand-in for
    Inception features, DESIGN.md §6)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import DQConfig
from repro.core.dqgan import DQGAN
from repro.data import gaussian_mixture_sampler
from repro.models.gan import GANConfig, clip_disc, gan_field_fn, mlp_gan_init, mlp_generate
from repro.strategy import Compression, ExchangePlan, Strategy


# Per-method distribution strategy (single-process: no worker axes) and
# optimizer/message pairing. The paper's baselines are points in the
# strategy lattice; anything schedule/participation-shaped is layered on
# via `strategy_overrides` below.
_SINGLE = ExchangePlan(kind="sim", worker_axes=())
METHOD_STRATEGIES = {
    "CPOAdam": Strategy(compression=Compression(compressor="identity",
                                                error_feedback=False),
                        exchange=_SINGLE),
    "CPOAdam-GQ": Strategy(compression=Compression(error_feedback=False),
                           exchange=_SINGLE),
    "DQGAN": Strategy(exchange=_SINGLE),
    "DQGAN-noEF": Strategy(compression=Compression(error_feedback=False),
                           exchange=_SINGLE),
}
METHODS = {
    # name: (optimizer, message)
    "CPOAdam": ("oadam", "grad"),
    "CPOAdam-GQ": ("oadam", "grad"),
    "DQGAN": ("omd", "update"),
    "DQGAN-noEF": ("omd", "update"),
}


# per-method default LRs ("chosen by an inspection of grid search results",
# paper §4): Adam-family needs a smaller step than plain OMD here.
METHOD_LR = {"CPOAdam": 1e-3, "CPOAdam-GQ": 1e-3, "DQGAN": 3e-3,
             "DQGAN-noEF": 3e-3}


def make_trainer(method: str, cfg: GANConfig, lr: float,
                 dq_overrides: dict | None = None,
                 strategy_overrides: dict | None = None,
                 mesh=None):
    opt, msg = METHODS[method]
    strat = METHOD_STRATEGIES[method]
    if strategy_overrides:
        strat = strat.evolve(**strategy_overrides)
    if mesh is not None and not strat.exchange.worker_axes:
        # multi-worker run (comm_adaptive frontier): the mesh's data axis
        # becomes the paper's M machines
        strat = strat.evolve(worker_axes=("data",))
    # Adam preconditioning normalizes the field-level critic boost away;
    # restore the n_critic=5 ratio post-preconditioning (TTUR).
    mults = (("disc", cfg.disc_grad_mult),) if opt in ("adam", "oadam") else ()
    dq = DQConfig.from_strategy(strat, optimizer=opt, message=msg, lr=lr,
                                lr_mults=mults)
    if dq_overrides:
        import dataclasses
        dq = dataclasses.replace(dq, **dq_overrides)
    batch_spec = None
    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        batch_spec = P(("data",))
    return DQGAN(field_fn=gan_field_fn(cfg), dq=dq, mesh=mesh,
                 batch_spec=batch_spec)


def frechet_distance(feats_a, feats_b):
    """Fréchet distance between Gaussians fit to two feature sets, with a
    diagonal-covariance approximation (stable without scipy sqrtm)."""
    mu_a, mu_b = feats_a.mean(0), feats_b.mean(0)
    va, vb = feats_a.var(0), feats_b.var(0)
    return float(np.sum((mu_a - mu_b) ** 2)
                 + np.sum(va + vb - 2 * np.sqrt(np.maximum(va * vb, 0))))


def random_features(key, x, dim=64):
    """Fixed random 2-layer projection as the stand-in feature extractor."""
    d = x.shape[-1]
    w1 = jax.random.normal(key, (d, 128)) / np.sqrt(d)
    w2 = jax.random.normal(jax.random.fold_in(key, 1), (128, dim)) / np.sqrt(128)
    return np.asarray(jnp.tanh(jnp.tanh(x @ w1) @ w2))


def eval_mixture_gan(params, cfg, sample_real, centers, key, n=2000):
    z = jax.random.normal(key, (n, cfg.latent_dim))
    fake = mlp_generate(params["gen"], cfg, z)
    real = sample_real(jax.random.fold_in(key, 1), n)
    d = jnp.linalg.norm(fake[:, None] - centers[None], axis=-1)
    nearest = jnp.min(d, axis=1)
    assign = jnp.argmin(d, axis=1)
    covered = int((np.bincount(np.asarray(assign), minlength=len(centers))
                   > n * 0.01).sum())
    hq = float(jnp.mean(nearest < 0.25))          # near a mode (5σ)
    fid = frechet_distance(random_features(jax.random.key(123), fake),
                           random_features(jax.random.key(123), real))
    return {"modes": covered, "hq_frac": round(hq, 3),
            "fid": round(fid, 4)}


def train_mixture_gan(method: str, steps=1500, batch=256, lr=None, seed=0,
                      eval_every=0, dq_overrides: dict | None = None,
                      strategy_overrides: dict | None = None,
                      mesh=None):
    """Train the 2-D mixture GAN; `strategy_overrides` patches the
    method's distribution strategy by legacy field name (e.g.
    {"schedule": "delayed", "staleness_tau": 4} for the convergence-vs-
    staleness frontier of `benchmarks.run --only sched`); `dq_overrides`
    patches optimizer-side DQConfig fields. `mesh` runs the workers over
    the mesh's data axis (the comm_adaptive frontier's M machines)."""
    from contextlib import nullcontext

    lr = METHOD_LR.get(method, 1e-3) if lr is None else lr
    cfg = GANConfig(name="mix", image_size=0, data_dim=2, latent_dim=16,
                    hidden=128, weight_clip=0.1)
    sample_real, centers = gaussian_mixture_sampler(n_modes=8)
    key = jax.random.key(seed)
    params = mlp_gan_init(key, cfg)
    tr = make_trainer(method, cfg, lr, dq_overrides, strategy_overrides,
                      mesh=mesh)
    with jax.set_mesh(mesh) if mesh is not None else nullcontext():
        st = tr.init(params)
        step = jax.jit(tr.step, static_argnums=(3,), donate_argnums=0)
        sched = tr.strategy.schedule.runtime()
        curve = []
        for i in range(steps):
            k = jax.random.fold_in(key, i)
            batch_data = {"real": sample_real(k, batch)}
            out = step(st, batch_data, k, sched.is_exchange_step(i))
            st = out.state
            st = st._replace(params=clip_disc(st.params, cfg))
            if eval_every and (i + 1) % eval_every == 0:
                m = eval_mixture_gan(st.params, cfg, sample_real, centers,
                                     jax.random.fold_in(key, 10_000 + i))
                m["step"] = i + 1
                curve.append(m)
        final = eval_mixture_gan(st.params, cfg, sample_real, centers,
                                 jax.random.fold_in(key, 999_999))
    return final, curve, st
