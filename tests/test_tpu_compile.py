"""Compiles for a described TPU v5e, no chip attached: the Pallas kernels of
the main path at real sizes, and full-width dcgan32 training steps, one of
them the benchmark's q8.b64 step with its named scopes on and off.

These catch what interpret mode cannot (tiling and VMEM refusals, a
primitive Mosaic does not lower) at no chip time. The topology is described
inside a module fixture and nowhere else: only one process may load the TPU
library, and every test worker imports every test file.
"""
import argparse
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.configs as cfgs
import repro.kernels.quantize as KQ
from repro.configs.base import DQConfig
from repro.core.dqgan import DQGAN
from repro.kernels.flash_attention import flash_attention
from repro import strategy as strategy_api
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


# --------------------------------------------------------------------------- #
# the benchmark's q8.b64 step: where its named scopes land on the chip
# --------------------------------------------------------------------------- #
Q8_B64 = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench", "traffic", "q8.b64.json")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _mix_strategy(flags):
    ap = argparse.ArgumentParser()
    strategy_api.add_strategy_args(ap)
    return strategy_api.strategy_from_args(ap.parse_args(flags),
                                           worker_axes=())


@pytest.fixture(scope="module")
def q8_b64_hlo(one_chip):
    """The full-width dcgan32 step as the benchmark's q8.b64 mix builds it
    (its flags, OMD, the update message), compiled for one described chip
    with the mix's --obs-spans (True) and without it (False)."""
    with open(Q8_B64) as fh:
        mix = json.load(fh)
    real = KQ.resolve_interpret
    KQ.resolve_interpret = lambda interpret=None: bool(interpret)
    try:
        cfg, bundle, params = _dcgan32()

        def on_chip(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

        texts = {}
        for spans in (True, False):
            flags = [f for f in mix["flags"] if spans or f != "--obs-spans"]
            dq = DQConfig.from_strategy(_mix_strategy(flags),
                                        optimizer="omd", lr=mix["lr"],
                                        message="update")
            tr = DQGAN(field_fn=bundle.field_fn, dq=dq)
            state = jax.tree.map(on_chip, tr.init_abstract(params))
            batch = {"real": on_chip(jax.ShapeDtypeStruct(
                (mix["batch_per_worker"], cfg.image_size, cfg.image_size,
                 cfg.channels), jnp.float32))}
            key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
            texts[spans] = jax.jit(
                tr.step, static_argnums=(3,), donate_argnums=(0,)).lower(
                    state, batch, key, True).compile().as_text()
    finally:
        KQ.resolve_interpret = real
    return texts


def _instructions(hlo_text):
    """(opcode-bearing line, op_name) of every instruction with metadata."""
    for line in hlo_text.splitlines():
        m = _OP_NAME.search(line)
        if m and " = " in line:
            yield line, m.group(1)


def test_q8_b64_step_scopes(q8_b64_hlo):
    """The quantize kernel and the exchange's uniform draws run under
    repro.obs/compress, the bucket concatenates under repro.obs/pack, both
    inside repro.obs/exchange; the OMD lookahead has its own scope."""
    ops = list(_instructions(q8_b64_hlo[True]))
    kernels = [n for line, n in ops if "tpu_custom_call" in line]
    assert len(kernels) == 3        # one per bucket
    assert all("repro.obs/exchange/repro.obs/compress/" in n
               for n in kernels), kernels
    draws = [n for _, n in ops
             if "repro.obs/exchange" in n and "jit(_uniform)" in n]
    assert draws and all("repro.obs/compress/" in n for n in draws)
    packs = [n for line, n in ops if " concatenate(" in line
             and "repro.obs/exchange" in n]
    assert packs and all("repro.obs/exchange/repro.obs/pack/" in n
                         for n in packs), packs
    assert any("repro.obs/lookahead/" in n for _, n in ops)
    assert any("repro.obs/apply/" in n for _, n in ops)


def test_q8_b64_step_scopes_change_only_metadata(q8_b64_hlo):
    """Spans on and off compile to the same program: equal once op
    metadata is stripped and instruction names are numbered by first
    use (a custom call takes its name from the innermost scope)."""
    def canonical(text):
        text = re.sub(r", metadata=\{[^}]*\}", "", text)
        names = {}
        return re.sub(r"%([^\s,(){}=]+)", lambda m: "%" + str(
            names.setdefault(m.group(1), len(names))), text)

    assert canonical(q8_b64_hlo[True]) == canonical(q8_b64_hlo[False])
    assert "repro.obs/" not in q8_b64_hlo[False]
