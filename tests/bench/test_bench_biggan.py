"""The BigGAN family (`bench/families/biggan.py`) against the program it
stands beside: the same weights, spectral-norm vectors, noise and field at
smoke size; a FLOP count that XLA's count of the compiled field confirms;
the parameter count of the model the program builds at full size; the
labelled pool; and the readers of its two per-layer metrics."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchkit

import run
from reference import PRECISION

CELL = "biggan128.q8.b32"


@pytest.fixture(scope="module")
def family():
    cfg, fam = benchkit.config("biggan128")
    return fam, cfg


def _program(fam, cfg):
    from repro.models import build

    return build(fam.program_config(cfg))


def _labelled(fam, cfg, rows, seed=7):
    return fam.make_pool(cfg, seed, 1, rows)[0]


def test_reference_field_is_the_programs(family):
    """At smoke size, from one key: the family's weights and u0 are the
    program's; its noise is the program's draw; and its field (each loss
    differentiated on its own) gives the program's losses, gradients by
    leaf and new spectral-norm vectors, on those weights in float64, where
    rounding cannot hide a difference (in float32 it reaches 4e-4 of the
    median leaf in G's first layers, amplified by five BatchNorms), with
    the hinge active on some rows and not on others, of the reals and of
    the fakes."""
    from repro.models import biggan

    fam, cfg = family
    cfg = fam.smoke(cfg)
    bundle = _program(fam, cfg)
    key, rng = jax.random.key(5), jax.random.key(5)
    params = jax.jit(bundle.init)(key)
    ours = fam.init_params(cfg, key)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(params)):
        assert a.shape == b.shape and float(jnp.max(jnp.abs(a - b))) < 1e-6
    u0 = bundle.field_fn.init_state(params)
    assert {k: np.asarray(v).tolist() for k, v in u0.items()} == {
        k: np.asarray(v).tolist()
        for k, v in fam.init_field_state(cfg, key).items()}
    for a, b in zip(fam.draw(cfg, rng, 8),
                    biggan.draw(fam.program_config(cfg), rng, 8)):
        assert np.array_equal(a, b)

    batch = _labelled(fam, cfg, 8)
    with jax.enable_x64(True):
        params, u0, batch = jax.tree.map(
            lambda x: x.astype(jnp.float64) if x.dtype == jnp.float32 else x,
            (params, u0, batch))
        g_prog, m_prog, u_prog = jax.jit(bundle.field_fn)(params, batch, rng,
                                                          u0)
        g_ref, loss, scale, u_ref = jax.jit(
            lambda p, s, b, n: fam.field(p, s, cfg, b, n,
                                         PRECISION["highest"], None))(
            params, u0, batch, fam.draw(cfg, rng, 8))
        for side in ("real", "fake"):
            assert 0 < float(m_prog[f"hinge_active_{side}"]) < 1
        # the reference's loss is taken in float32
        assert abs(float(loss) - float(m_prog["loss"])) <= \
            1e-6 * float(scale)
        prog, ref = jax.tree.leaves(g_prog), jax.tree.leaves(g_ref)
        norms = [float(jnp.linalg.norm(x)) for x in ref]
        median = sorted(norms)[len(norms) // 2]
        assert median > 0
        for a, b, n in zip(prog, ref, norms):
            assert a.shape == b.shape and a.dtype == jnp.float64
            assert float(jnp.linalg.norm(a - b)) <= 1e-10 * max(n, median)
        assert set(u_prog) == set(u_ref)
        for k in u_ref:
            assert float(jnp.max(jnp.abs(u_prog[k] - u_ref[k]))) < 1e-12, k


def _compiled_flops(fam, cfg, batch):
    bundle = _program(fam, cfg)
    params = jax.eval_shape(bundle.init, jax.random.key(0))
    state = jax.eval_shape(bundle.field_fn.init_state, params)
    rows = {"real": jax.ShapeDtypeStruct((batch, 128, 128, 3), jnp.float32),
            "label": jax.ShapeDtypeStruct((batch,), jnp.int32)}
    compiled = jax.jit(bundle.field_fn).lower(
        params, rows, jax.random.key(0), state).compile()
    return compiled.cost_analysis()["flops"]


def test_step_flops_against_compiled_count(family):
    """The model count leaves out only elementwise work: within 2% of (and
    below) XLA's count of the program's compiled field at the published
    widths, batch 8 (lowered from shapes alone). At smoke size, where
    elementwise work weighs more beside 8-wide convolutions, below it."""
    fam, cfg = family
    compiled = _compiled_flops(fam, cfg, 8)
    model = fam.step_flops(cfg, 8, 1)
    assert model <= compiled and (compiled - model) / compiled < 0.02
    smoke = fam.smoke(cfg)
    assert fam.step_flops(smoke, 8, 1) <= _compiled_flops(fam, smoke, 8)


def test_n_params_at_full_size(family):
    """The family's sizes are those of the tree the program builds at the
    published widths (abstract evaluation only): 70,433,988 in G and
    87,982,370 in D, as BigGAN's Table 1 gives (70.4 M and 87.9 M)."""
    fam, cfg = family
    shapes = jax.eval_shape(_program(fam, cfg).init, jax.random.key(0))
    sizes = [math.prod(x.shape) for x in jax.tree.leaves(shapes)]
    assert sizes == fam.param_sizes(cfg)
    assert fam.n_params(cfg) == cfg["n_params"] == 158_416_358
    per_net = {k: sum(math.prod(x.shape) for x in jax.tree.leaves(v))
               for k, v in shapes.items()}
    assert per_net == {"gen": 70_433_988, "disc": 87_982_370}


def test_pool_labels_set_the_colour(family):
    """Labelled 128x128 images in [-1, 1], labels in range; the same seed
    gives the same pool; an image's colour follows its label."""
    fam, cfg = family
    cfg = fam.smoke(cfg)
    a, b = _labelled(fam, cfg, 64), _labelled(fam, cfg, 64)
    assert a["real"].shape == (64, 128, 128, 3)
    assert a["label"].shape == (64,) and a["label"].dtype == jnp.int32
    assert np.array_equal(a["real"], b["real"])
    assert np.array_equal(a["label"], b["label"])
    assert float(jnp.max(jnp.abs(a["real"]))) <= 1.0
    lab = np.asarray(a["label"])
    assert lab.min() >= 0 and lab.max() < cfg["biggan_config"]["n_classes"]
    assert len(set(lab.tolist())) > 1
    # the blob's colour: the brightest pixel's channels, over its sum
    img = np.asarray(a["real"]).reshape(64, -1, 3)
    top = img[np.arange(64), img.sum(axis=2).argmax(axis=1)] + 1.0
    hue = top / top.sum(axis=1, keepdims=True)
    for c in set(lab.tolist()):
        same = hue[lab == c]
        assert np.ptp(same, axis=0).max() < 0.1, c
    assert not np.array_equal(_labelled(fam, cfg, 64, seed=8)["label"], lab)


def _trace(ops):
    hlo = {"a1": {"op_name": "jit(step)/repro.obs/field/repro.obs/attention/"
                  "dot_general"},
           "a2": {"op_name": "jit(step)/repro.obs/field/transpose(jvp())/"
                  "repro.obs/attention/dot_general"},
           "s1": {"op_name": "jit(step)/repro.obs/field/"
                  "repro.obs/spectral_norm/dot_general"},
           "f1": {"op_name": "jit(step)/repro.obs/field/conv"}}
    return {"window_ns": [0.0, 1e6], "steps": 2, "chips": 1,
            "ops": ops, "host": [], "hlo": hlo}


def test_readers_of_attention_and_spectral_norm():
    """Per step, the device time of the ops under each scope, forward and
    backward; nothing where no op carries it, as in a program without
    BigGAN."""
    spec = run.resolve(benchkit.REPO, CELL)
    names = {m["name"] for m in spec["per_layer"]}
    assert {"attention_ms", "spectral_norm_ms"} <= names
    attn = run.reader(spec["metrics_dir"], "attention_ms")
    sn = run.reader(spec["metrics_dir"], "spectral_norm_ms")
    tr = _trace([[0, "a1", 0.0, 3e5], [0, "a2", 2e5, 2e5],
                 [0, "s1", 5e5, 1e5], [0, "f1", 6e5, 4e5],
                 [0, "s1", 2e6, 1e5]])          # after the window
    assert attn({"trace": tr}) == pytest.approx(0.2)
    assert sn({"trace": tr}) == pytest.approx(0.05)
    bare = _trace([[0, "f1", 0.0, 4e5]])
    assert attn({"trace": bare}) is None and sn({"trace": bare}) is None
