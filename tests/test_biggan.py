"""BigGAN-128 (models/biggan.py) and its hinge field: the published sizes,
each layer against a plain loop, and the field against the two losses
differentiated on their own. Its state through training is
test_biggan_train.py's."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as cfgs
from repro.data import labelled_images
from repro.models import biggan
from repro.models.gan import gan_field_fn

SMOKE = cfgs.get("biggan128").reduced()


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def test_published_sizes():
    """70.4 M parameters in G and 87.98 M in D (the paper's Table 1 row
    reads 70.4 and 87.9), 64 spectrally normalized weights."""
    params = jax.eval_shape(lambda k: biggan.init(k, cfgs.get("biggan128")),
                            jax.random.key(0))
    n = {k: sum(x.size for x in jax.tree.leaves(v))
         for k, v in params.items()}
    assert n == {"gen": 70_433_988, "disc": 87_982_370}
    assert len(biggan.sn_leaves(params)) == 64


@pytest.mark.parametrize("path,shape", [("disc/block1/conv1/w",
                                         (3, 3, 6, 12)),
                                        ("disc/proj", (10, 24))])
def test_power_iteration_finds_the_top_singular_value(path, shape):
    """Power iterations carried in the state converge to the largest
    singular value of W_mat, and the normalized weight's is then 1."""
    key = jax.random.key(1)
    w = jax.random.normal(key, shape)
    params = {"disc": {}}
    node = params["disc"]
    names = path.split("/")[1:]
    for n in names[:-1]:
        node = node.setdefault(n, {})
    node[names[-1]] = w
    state = biggan.init_sn_state(params)
    assert list(state) == [path]
    for _ in range(200):
        normed, state = biggan.spectral_norm(params, state, 1e-6)
    w_mat = np.asarray(w) if path.endswith("proj") else \
        np.asarray(w).reshape(-1, shape[-1]).T
    assert state[path].shape == (w_mat.shape[0],)
    top = np.linalg.svd(w_mat, compute_uv=False)[0]
    got = normed["disc"]
    for n in names:
        got = got[n]
    assert float(jnp.max(jnp.abs(got * top - w))) < 1e-4 * float(
        jnp.max(jnp.abs(w)))


def test_ccbn_against_a_per_class_loop():
    """Each row normalized by the batch's statistics, then scaled by
    1 + W_g e_c and shifted by W_b e_c of its class c."""
    ks = jax.random.split(jax.random.key(2), 5)
    x = jax.random.normal(ks[0], (6, 4, 4, 5)) * 3 + 1
    emb = jax.random.normal(ks[1], (3, 7))
    labels = jnp.array([0, 2, 1, 2, 0, 1])
    p = {"gain": jax.random.normal(ks[2], (7, 5)),
         "bias": jax.random.normal(ks[3], (7, 5))}
    out = biggan.ccbn(p, x, emb[labels], 1e-5)
    xs = np.asarray(x, np.float64)
    mean = xs.mean(axis=(0, 1, 2))
    var = xs.var(axis=(0, 1, 2))
    for c in range(3):
        gain = 1 + np.asarray(emb[c]) @ np.asarray(p["gain"])
        bias = np.asarray(emb[c]) @ np.asarray(p["bias"])
        for i in np.flatnonzero(np.asarray(labels) == c):
            want = (xs[i] - mean) / np.sqrt(var + 1e-5) * gain + bias
            assert np.allclose(out[i], want, atol=1e-4)


def test_attention_against_a_naive_loop():
    ks = jax.random.split(jax.random.key(3), 6)
    b, h, w, c = 2, 4, 6, 16
    x = jax.random.normal(ks[0], (b, h, w, c))
    p = {"theta": jax.random.normal(ks[1], (1, 1, c, c // 8)),
         "phi": jax.random.normal(ks[2], (1, 1, c, c // 8)),
         "g": jax.random.normal(ks[3], (1, 1, c, c // 2)),
         "o": jax.random.normal(ks[4], (1, 1, c // 2, c)),
         "gamma": jnp.float32(0.7)}
    out = biggan.attention(p, x)
    xs = np.asarray(x, np.float64)
    theta = xs @ np.asarray(p["theta"][0, 0])
    phi = xs @ np.asarray(p["phi"][0, 0])
    g = xs @ np.asarray(p["g"][0, 0])

    def pool(a, i, j):
        return a[2 * i:2 * i + 2, 2 * j:2 * j + 2].max(axis=(0, 1))

    for n in range(b):
        keys = [(pool(phi[n], i, j), pool(g[n], i, j))
                for i in range(h // 2) for j in range(w // 2)]
        for i in range(h):
            for j in range(w):
                s = np.array([theta[n, i, j] @ k for k, _ in keys])
                a = np.exp(s - s.max())
                a /= a.sum()
                o = sum(ak * v for ak, (_, v) in zip(a, keys))
                want = 0.7 * o @ np.asarray(p["o"][0, 0]) + xs[n, i, j]
                assert np.allclose(out[n, i, j], want, rtol=1e-4,
                                   atol=1e-4)


def test_hinge_field_against_the_two_losses():
    """The field's two backward passes of D on the fakes (L_D's
    weight-gradients under the hinge mask, L_G's input-gradients under
    -1/B) give what `jax.value_and_grad` of each loss gives on its own,
    through the same power iteration, on a batch where the mask holds
    both 0 and 1 on each side."""
    params = biggan.init(jax.random.key(5), SMOKE)
    real, label = labelled_images(jax.random.key(5), 8, 128, 3,
                                  SMOKE.n_classes)
    batch, rng = {"real": real, "label": label}, jax.random.key(6)
    field = gan_field_fn(SMOKE)
    state = field.init_state(params)
    grads, metrics, new_state = jax.jit(field)(params, batch, rng, state)
    for side in ("real", "fake"):
        assert 0 < float(metrics[f"hinge_active_{side}"]) < 1, metrics

    z, y_fake = biggan.draw(SMOKE, rng, 8)
    eps = SMOKE.SN_eps

    def sn(p):
        return biggan.spectral_norm(p, state, eps)[0]

    def loss_g(gen):
        p = sn({"gen": gen, "disc": params["disc"]})
        fake = biggan.generate(p["gen"], SMOKE, z, y_fake)
        return -jnp.mean(biggan.discriminate(p["disc"], SMOKE, fake, y_fake))

    def loss_d(disc):
        p = sn({"gen": params["gen"], "disc": disc})
        fake = jax.lax.stop_gradient(
            biggan.generate(p["gen"], SMOKE, z, y_fake))
        d_real = biggan.discriminate(p["disc"], SMOKE, batch["real"],
                                     batch["label"])
        d_fake = biggan.discriminate(p["disc"], SMOKE, fake, y_fake)
        return (jnp.mean(jax.nn.relu(1 - d_real))
                + jnp.mean(jax.nn.relu(1 + d_fake)))

    lg, g_gen = jax.jit(jax.value_and_grad(loss_g))(params["gen"])
    ld, g_disc = jax.jit(jax.value_and_grad(loss_d))(params["disc"])
    assert float(metrics["loss_g"]) == pytest.approx(float(lg), rel=1e-5)
    assert float(metrics["loss_d"]) == pytest.approx(float(ld), rel=1e-5)
    want = {"gen": g_gen, "disc": jax.tree.map(
        lambda x: SMOKE.disc_grad_mult * x, g_disc)}
    norms = [float(jnp.linalg.norm(x)) for x in jax.tree.leaves(want)]
    median = float(np.median(norms))
    for a, b, n in zip(jax.tree.leaves(grads), jax.tree.leaves(want), norms):
        assert float(jnp.linalg.norm(a - b)) <= 1e-4 * max(n, median)
    _, direct = biggan.spectral_norm(params, state, eps)
    for k in state:
        assert _rel(new_state[k], direct[k]) < 1e-5, k


def test_init_is_orthogonal():
    """Every kernel, linear and embedding starts orthogonal: its (rows,
    outputs) matrix has orthonormal columns, or rows where it is wide; the
    orthogonal init of one normal draw is `jax.nn.initializers.orthogonal`
    of the same values."""
    params = biggan.init(jax.random.key(4), SMOKE)
    for path, w in jax.tree_util.tree_flatten_with_path(params)[0]:
        if w.ndim < 2:
            continue
        m = w.reshape(-1, w.shape[-1])
        g = m.T @ m if m.shape[0] >= m.shape[1] else m @ m.T
        assert float(jnp.max(jnp.abs(g - jnp.eye(g.shape[0])))) < 1e-4, path
    key = jax.random.key(9)
    ref = jax.nn.initializers.orthogonal()(key, (3, 3, 4, 6), jnp.float32)
    a = jax.random.normal(key, (36, 6)).reshape(-1)
    assert float(jnp.max(jnp.abs(biggan.orthogonal(a, (3, 3, 4, 6)) - ref))) \
        < 1e-6
