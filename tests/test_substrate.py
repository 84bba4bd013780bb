"""Substrate tests: data pipeline, checkpointing, optimizers, sharding
rules, dry-run utilities, GAN field."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (pip install .[dev])")
from hypothesis import given, settings, strategies as st  # noqa: E402

import repro.configs as cfgs
from repro import checkpoint, optim
from repro.configs.base import SHAPES, DQConfig
from repro.core.dqgan import DQGAN
from repro.data import (gaussian_mixture_sampler, lm_batch_iterator,
                        procedural_images, synthetic_lm_batch)
from repro.models import build
from repro.models.gan import GANConfig, clip_disc

KEY = jax.random.key(0)


# ------------------------------- data -------------------------------------- #
def test_lm_batch_shapes_and_determinism():
    b1 = synthetic_lm_batch(KEY, 4, 16, 100)
    b2 = synthetic_lm_batch(KEY, 4, 16, 100)
    assert b1["tokens"].shape == (4, 16) and b1["tokens"].dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    assert int(jnp.max(b1["targets"])) < 100
    # targets are the next-step stream of tokens
    np.testing.assert_array_equal(np.asarray(b1["tokens"][:, 1:]),
                                  np.asarray(b1["targets"][:, :-1]))


def test_lm_iterator_advances():
    it = lm_batch_iterator(0, 2, 8, 50)
    a, b = next(it), next(it)
    assert not np.array_equal(np.asarray(a["tokens"]), np.asarray(b["tokens"]))


def test_gaussian_mixture_covers_modes():
    sample, centers = gaussian_mixture_sampler(n_modes=8)
    pts = sample(KEY, 4000)
    d = jnp.linalg.norm(pts[:, None] - centers[None], axis=-1)
    assign = jnp.argmin(d, axis=1)
    counts = np.bincount(np.asarray(assign), minlength=8)
    assert (counts > 100).all()


def test_procedural_images_range():
    imgs = procedural_images(KEY, 8, size=32)
    assert imgs.shape == (8, 32, 32, 3)
    assert float(jnp.min(imgs)) >= -1 and float(jnp.max(imgs)) <= 1
    # nontrivial variance across images (structured, not constant)
    assert float(jnp.std(imgs)) > 0.05


# ---------------------------- checkpoint ----------------------------------- #
def test_checkpoint_roundtrip(tmp_path):
    tree = {
        "a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
        "b": {"c": jnp.ones((4,), jnp.bfloat16), "d": jnp.zeros((), jnp.int32)},
        "lst": [jnp.full((2,), 7.0)],
    }
    p = str(tmp_path / "ck.npz")
    checkpoint.save(p, tree, step=42)
    assert checkpoint.latest_step(p) == 42
    back = checkpoint.restore(p, jax.eval_shape(lambda: tree))
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


def test_checkpoint_dqgan_state_roundtrip(tmp_path):
    cfg = cfgs.get("gemma-2b").reduced()
    bundle = build(cfg)
    params = bundle.init(KEY, 32)
    tr = DQGAN(field_fn=bundle.field_fn,
               dq=DQConfig(optimizer="omd", compressor="qsgd8_linf",
                           exchange="sim", worker_axes=()))
    st = tr.init(params)
    p = str(tmp_path / "state.npz")
    checkpoint.save(p, st, step=0)
    back = checkpoint.restore(p, jax.eval_shape(lambda: st))
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(st.params)[0]),
        np.asarray(jax.tree.leaves(back.params)[0]))


# ---------------------------- optimizers ----------------------------------- #
@pytest.mark.parametrize("name", ["sgd", "adam", "oadam"])
def test_single_machine_optimizers(name):
    opt = optim.REGISTRY[name](0.1)
    params = {"w": jnp.full((8,), 5.0)}
    state = opt.init(params)
    for _ in range(300):
        grads = {"w": 2 * params["w"]}
        params, state = opt.update(grads, state, params)
    assert float(jnp.linalg.norm(params["w"])) < 1e-2


def test_cosine_schedule_shape():
    sch = optim.cosine_lr(1.0, warmup=10, total=100)
    assert float(sch(0)) == 0.0
    assert abs(float(sch(10)) - 1.0) < 1e-6
    assert float(sch(100)) < 0.01


# ---------------------------- sharding rules -------------------------------- #
def test_param_specs_consistency():
    from jax.sharding import PartitionSpec as P
    from repro.parallel import sharding as shd

    cfg = cfgs.get("gemma-2b")
    bundle = build(cfg)
    params = jax.eval_shape(lambda: bundle.init(KEY, 8))
    for mode in ("dp", "fsdp"):
        specs = shd.param_specs(params, cfg, mode)
        flat_p = jax.tree.leaves(params)
        flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        assert len(flat_p) == len(flat_s)
        for leaf, spec in zip(flat_p, flat_s):
            assert len(spec) <= leaf.ndim, (spec, leaf.shape)


def test_sanitize_spec_drops_indivisible():
    from jax.sharding import PartitionSpec as P
    from repro.parallel.sharding import sanitize_spec

    class FakeMesh:
        shape = {"model": 16, "data": 16}

    s = sanitize_spec(P("model", None), (51865, 384), FakeMesh)
    assert s == P(None, None)
    s = sanitize_spec(P("model", None), (256000, 384), FakeMesh)
    assert s == P("model", None)
    s = sanitize_spec(P(("data", "model"),), (512,), FakeMesh)
    assert s == P(("data", "model"))
    s = sanitize_spec(P(("data", "model"),), (128,), FakeMesh)
    assert s == P(None)


# ---------------------------- dry-run utils -------------------------------- #
def test_parse_collective_bytes():
    from repro.launch.dryrun import parse_collective_bytes

    hlo = """
  %ag = s8[16,1024]{1,0} all-gather(s8[2,1024] %x), replica_groups={}
  %ar = (f32[512]{0}, f32[16]{0}) all-reduce(...), to_apply=%add
  %a2a.1 = s8[8,128]{1,0} all-to-all(s8[8,128] %y), dimensions={0}
  %ag2 = bf16[4,256]{1,0} all-gather-start(bf16[1,256] %z)
  %agd = bf16[4,256]{1,0} all-gather-done(bf16[4,256] %ag2)
"""
    c = parse_collective_bytes(hlo)
    assert c["all-gather"]["count"] == 2
    assert c["all-gather"]["bytes"] == 16 * 1024 + 4 * 256 * 2
    assert c["all-gather"]["int8_bytes"] == 16 * 1024
    assert c["all-reduce"]["bytes"] == (512 + 16) * 4
    assert c["all-to-all"]["int8_bytes"] == 8 * 128


def test_model_flops_and_applicability():
    from repro.launch.dryrun import applicable, model_flops

    cfg = cfgs.get("gemma-2b")
    tr = SHAPES["train_4k"]
    assert model_flops(cfg, tr) == 6.0 * cfg.param_count() * 256 * 4096
    moe = cfgs.get("qwen3-moe-30b-a3b")
    assert model_flops(moe, tr) < 6.0 * moe.param_count() * 256 * 4096
    assert applicable(cfgs.get("yi-34b"), SHAPES["long_500k"])[0] is False
    assert applicable(cfgs.get("mamba2-1.3b"), SHAPES["long_500k"])[0] is True
    assert applicable(cfgs.get("recurrentgemma-2b"), SHAPES["long_500k"])[0] is True


def test_exchange_modeled_wire_bytes():
    from repro.core import compressors as C
    from repro.core.exchange import modeled_wire_bytes

    shape = (1 << 20,)
    comp = C.get("qsgd8_linf")
    full = modeled_wire_bytes("exact", comp, shape, 32)
    two = modeled_wire_bytes("two_phase", comp, shape, 32)
    assert two < full / 3.5  # ~4x reduction at 8 bits


# ------------------------------- GAN ---------------------------------------- #
def test_gan_field_and_clip():
    from repro.models.gan import gan_field_fn, mlp_gan_init

    cfg = GANConfig(name="toy", image_size=0, latent_dim=8, hidden=32)
    params = mlp_gan_init(KEY, cfg)
    field = gan_field_fn(cfg)
    batch = {"real": jax.random.normal(KEY, (16, 2))}
    grads, metrics = jax.jit(field)(params, batch, KEY)
    assert set(grads) == {"gen", "disc"}
    assert all(jnp.all(jnp.isfinite(g)) for g in jax.tree.leaves(grads))
    clipped = clip_disc(params, cfg)
    for leaf in jax.tree.leaves(clipped["disc"]):
        assert float(jnp.max(jnp.abs(leaf))) <= cfg.weight_clip + 1e-7


def test_dcgan_shapes():
    from repro.models.gan import dcgan_discriminate, dcgan_generate, dcgan_init

    cfg = GANConfig(image_size=32, channels=3, latent_dim=16, base_width=8)
    p = dcgan_init(KEY, cfg)
    z = jax.random.normal(KEY, (4, 16))
    imgs = dcgan_generate(p["gen"], cfg, z)
    assert imgs.shape == (4, 32, 32, 3)
    score = dcgan_discriminate(p["disc"], cfg, imgs)
    assert score.shape == (4,)


def test_compile_cache_dir(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code;
    otherwise the cache goes to the fixed .jax_cache/ at the repo root."""
    import os

    from repro.launch import compile_cache as cc

    updates = []
    monkeypatch.setattr(cc.jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "cache-from-env")
    assert cc.enable_compile_cache() == "cache-from-env"
    assert updates == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert cc.enable_compile_cache() == cc.CACHE_DIR
    assert updates == [("jax_compilation_cache_dir", cc.CACHE_DIR)]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cc.CACHE_DIR == os.path.join(repo, ".jax_cache")
