"""Compiles for a described TPU v5e, no chip attached: the Pallas kernels of
the main path at real sizes, and one full-width dcgan32 training step.

These catch what interpret mode cannot (tiling and VMEM refusals, a
primitive Mosaic does not lower) at no chip time. The topology is described
inside a module fixture and nowhere else: only one process may load the TPU
library, and every test worker imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.configs as cfgs
import repro.kernels.quantize as KQ
from repro.configs.base import DQConfig
from repro.core.dqgan import DQGAN
from repro.kernels.flash_attention import flash_attention
from repro.models import build
from repro.strategy import Compression, Strategy

COMPRESSED = Compression(plan="uniform", compressor="qsgd8_block1024")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep these out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _dcgan32():
    cfg = cfgs.get("dcgan32")
    bundle = build(cfg)
    params = jax.eval_shape(lambda k: bundle.init(k, max_seq=64),
                            jax.random.key(0))
    return cfg, bundle, params


def _bucket_sizes():
    _, _, params = _dcgan32()
    shapes = jax.tree.map(lambda x: tuple(x.shape), params)
    layout, _ = COMPRESSED.build(shapes, None, 1)
    return [b.size for b in layout.buckets]


@pytest.mark.parametrize("levels", ["static", "dynamic"])
@pytest.mark.parametrize("bucket", [0, 1, 2, "300rows"])
def test_quantize_ef_flat_compiles(one_chip, bucket, levels):
    """Every dcgan32 bucket, and a 300-row bucket: 300 has no divisor that
    is a multiple of 8 up to 256, so its last block is ragged."""
    n = 300 * 1024 if bucket == "300rows" else _bucket_sizes()[bucket]
    vec = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    if levels == "static":
        fn = jax.jit(lambda g, e, r: KQ.quantize_ef_flat(
            g, e, r, interpret=False))
        lowered = fn.lower(vec, vec, vec)
    else:
        fn = jax.jit(lambda g, e, r, lv: KQ.quantize_ef_flat(
            g, e, r, levels=lv, interpret=False))
        lowered = fn.lower(vec, vec, vec, jax.ShapeDtypeStruct(
            (), jnp.float32, sharding=one_chip))
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_flash_attention_compiles(one_chip):
    x = jax.ShapeDtypeStruct((16, 2048, 128), jnp.bfloat16, sharding=one_chip)
    fn = jax.jit(lambda q, k, v: flash_attention(q, k, v, interpret=False))
    assert "tpu_custom_call" in fn.lower(x, x, x).compile().as_text()


def test_dcgan32_bucketed_step_compiles(one_chip, monkeypatch):
    """One full-width dcgan32 step (the paper's OMD + EF, bucketed
    qsgd8_block1024) for one described chip. The process's backend is the
    CPU, so the kernel dispatch is steered to compiled mode here."""
    monkeypatch.setattr(KQ, "resolve_interpret",
                        lambda interpret=None: bool(interpret))
    cfg, bundle, params = _dcgan32()
    dq = DQConfig.from_strategy(Strategy(compression=COMPRESSED),
                                optimizer="omd", lr=2e-4)
    tr = DQGAN(field_fn=bundle.field_fn, dq=dq)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    state = jax.tree.map(on_chip, tr.init_abstract(params))
    batch = {"real": on_chip(jax.ShapeDtypeStruct(
        (64, cfg.image_size, cfg.image_size, cfg.channels), jnp.float32))}
    key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    compiled = jax.jit(tr.step, static_argnums=(3,)).lower(
        state, batch, key, True).compile()
    assert "tpu_custom_call" in compiled.as_text()
