"""The BigGAN family: BigGAN at 128x128 under the hinge loss.

What the harness needs to know of this architecture: the program's model
config, the data, the plain reference of the field, the model FLOPs and the
parameter sizes, and the smoke size of the CPU tests. The configuration
file's `biggan_config` holds BigGAN-PyTorch's settings (`G_ch`, `D_ch`,
`dim_z`, `shared_dim`, `n_classes`, `G_attn`, `D_attn`, `SN_eps`, `BN_eps`)
and `disc_grad_mult`.

Data: 128x128 images of a Gaussian blob on a gradient, in [-1, 1], each
coloured by its class, a seeded label in [0, n_classes): the batch
`{"real": (rows, 128, 128, 3), "label": (rows,)}`.

Reference: written from BigGAN-PyTorch (`BigGAN.py`, `layers.py`,
`losses.py`) in plain `jax.numpy` and `jax.lax`, at the matmul precision
the configuration states. G: hierarchical z (one chunk for the input
linear, one per block), a shared class embedding without spectral norm,
residual up-blocks with class-conditional BatchNorm on the batch's own
statistics (gain 1 + W_g y, bias W_b y, y = [shared(c), z chunk]), nearest
upsampling, self-attention after the block whose output is `G_attn` wide,
then BatchNorm, relu, a 3x3 convolution and tanh. D: residual down-blocks
(the first without pre-activation), self-attention after the block at
`D_attn`, relu, a sum over space, a linear and the class projection. Every
weight but the shared embedding is spectrally normalized with one power
iteration from the persisted vector u (the field's state, one per weight
and worker), u and v without gradient, sigma = v W u' with its gradient
through W. The field, with the noise (z and the fakes' classes) of each
row:

    [grad L_G in G, disc_grad_mult x grad L_D in D],
    L_D = E relu(1 - D(x, c)) + E relu(1 + D(G(z, c_f), c_f)),
    L_G = -E D(G(z, c_f), c_f),

each loss differentiated on its own by `jax.value_and_grad`, through the
normalized weights that one power iteration at the field's point gives.
The loss the comparison reads is L_D + L_G, its scale mean |D(x)| on the
reals.

Model FLOPs of one field evaluation (`hinge_field_fn`), each product the
field needs counted once, taps that land on padding left out:

  forward:  G on z; D on the fakes; D on the reals.
  L_D:      D weight-gradients on the reals and on the fakes (with the
            input-gradients inside D that they need: every product but
            those that read the image).
  L_G:      D input-gradients on the fakes down to the image (the hinge
            gives them another cotangent than L_D's, so the pass runs
            again); G weight-gradients and input-gradients (every product
            but the input linear, whose input is z; the ccbn linears'
            input-gradients reach the shared embedding).
  spectral norm: three matrix-vector products per normalized weight (the
            power iteration's two and sigma's), once per worker.

A product of two activations (attention's theta phi^T and beta g) needs
both operands' gradients in every backward pass. Counted: the 3x3 and 1x1
convolutions (on the upsampled maps in G), attention's two products and its
1x1s, the linears, the ccbn linears and the class projection. Not counted:
elementwise work, pooling, upsampling, BatchNorm statistics and the
embedding gather. One FLOP is one multiply or one add.
"""
from __future__ import annotations

import copy
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.biggan import BigGANConfig

import pool
from reference import operand

RESOLUTION = 128
CHANNELS = 3
BOTTOM = 4           # G's first feature map is 4x4
U0_SEED = 0          # the u0 vectors: cuts of one normal draw from this key
DN = ("NHWC", "HWIO", "NHWC")
# (in, out) width multiples of ch per block, 0 for the image's channels
G_MULTS = ((16, 16), (16, 8), (8, 4), (4, 2), (2, 1))
D_MULTS = ((0, 1), (1, 2), (2, 4), (4, 8), (8, 16), (16, 16))
SMOKE = {"G_ch": 8, "D_ch": 8, "n_classes": 10, "shared_dim": 16}


def program_config(config: dict) -> BigGANConfig:
    """The `BigGANConfig` a configuration file describes."""
    return BigGANConfig(**config["biggan_config"])


def smoke(config: dict) -> dict:
    """A copy of `config` at the CPU tests' size: every kind of layer at
    128x128, ch 8 (the narrowest at which D's attention keeps a query
    width of 1), 10 classes, a shared embedding of 16."""
    out = copy.deepcopy(config)
    out["biggan_config"].update(SMOKE)
    return out


# --------------------------------------------------------------------------- #
# data
# --------------------------------------------------------------------------- #
def labelled_images(key, n, size, n_classes):
    """(images, labels): labels uniform in [0, n_classes); each image a
    Gaussian blob at a random place and size on a gradient, its colour set
    by its label. Values in [-1, 1]."""
    kl, kx, ky, ks = jax.random.split(key, 4)
    label = jax.random.randint(kl, (n,), 0, n_classes)
    cx = jax.random.uniform(kx, (n, 1, 1, 1), minval=0.25, maxval=0.75)
    cy = jax.random.uniform(ky, (n, 1, 1, 1), minval=0.25, maxval=0.75)
    sig = jax.random.uniform(ks, (n, 1, 1, 1), minval=0.05, maxval=0.15)
    yy, xx = jnp.meshgrid(jnp.linspace(0, 1, size), jnp.linspace(0, 1, size),
                          indexing="ij")
    gx, gy = xx[None, :, :, None], yy[None, :, :, None]
    blob = jnp.exp(-((gx - cx) ** 2 + (gy - cy) ** 2) / (2 * sig**2))
    hue = (label / n_classes).reshape(n, 1, 1, 1)
    color = 0.5 + 0.5 * jnp.sin(
        2 * math.pi * (hue + jnp.arange(CHANNELS) / CHANNELS))
    img = blob * color + 0.1 * (gx + gy) - 0.5
    return jnp.clip(2 * img, -1, 1), label


def _batch(key, rows, n_classes):
    real, label = labelled_images(key, rows, RESOLUTION, n_classes)
    return {"real": real, "label": label}


def make_pool(config: dict, seed: int, n_batches: int, rows: int):
    """`n_batches` distinct batches of `rows` labelled images, as a list of
    {"real": (rows, 128, 128, 3), "label": (rows,)} device arrays."""
    return pool.make_pool(seed, n_batches, rows, _batch,
                          config["biggan_config"]["n_classes"])


# --------------------------------------------------------------------------- #
# parameters
# --------------------------------------------------------------------------- #
def _blocks(bc):
    """G's blocks [(in, out, output resolution)] and D's [(in, out,
    downsample, output resolution)]."""
    g, res = [], BOTTOM
    for a, b in G_MULTS:
        res *= 2
        g.append((a * bc["G_ch"], b * bc["G_ch"], res))
    d, res = [], RESOLUTION
    for i, (a, b) in enumerate(D_MULTS):
        down = i < len(D_MULTS) - 1
        res = res // 2 if down else res
        d.append((a * bc["D_ch"] if a else CHANNELS, b * bc["D_ch"], down,
                  res))
    return g, d


def _attn_at(bc):
    """(index of G's block followed by attention, its width; the same for
    D)."""
    g, d = _blocks(bc)
    gi = next(i for i, b in enumerate(g) if b[2] == bc["G_attn"])
    di = next(i for i, b in enumerate(d) if b[3] == bc["D_attn"])
    return gi, g[gi][1], di, d[di][1]


def _z_chunk(bc):
    return bc["dim_z"] // (len(G_MULTS) + 1)


def shapes(config: dict):
    """The parameter tree's shapes (tuples); a key names each layer as
    BigGAN-PyTorch does."""
    bc = config["biggan_config"]
    g, d = _blocks(bc)
    _, g_attn, _, d_attn = _attn_at(bc)
    y_dim = bc["shared_dim"] + _z_chunk(bc)

    def conv(k, cin, cout):
        return {"w": (k, k, cin, cout), "b": (cout,)}

    def attn(c):
        return {"theta": (1, 1, c, c // 8), "phi": (1, 1, c, c // 8),
                "g": (1, 1, c, c // 2), "o": (1, 1, c // 2, c), "gamma": ()}

    top = 16 * bc["G_ch"] * BOTTOM * BOTTOM
    gen = {"shared": (bc["n_classes"], bc["shared_dim"]),
           "linear": {"w": (_z_chunk(bc), top), "b": (top,)},
           "attn": attn(g_attn),
           "final": {"bn": {"gain": (bc["G_ch"],), "bias": (bc["G_ch"],)},
                     "conv": conv(3, bc["G_ch"], CHANNELS)}}
    for i, (cin, cout, _) in enumerate(g):
        gen[f"block{i}"] = {
            "bn1": {"gain": (y_dim, cin), "bias": (y_dim, cin)},
            "conv1": conv(3, cin, cout),
            "bn2": {"gain": (y_dim, cout), "bias": (y_dim, cout)},
            "conv2": conv(3, cout, cout),
            "conv_sc": conv(1, cin, cout)}
    c_top = d[-1][1]
    disc = {"attn": attn(d_attn), "linear": {"w": (c_top, 1), "b": (1,)},
            "proj": (bc["n_classes"], c_top)}
    for i, (cin, cout, down, _) in enumerate(d):
        disc[f"block{i}"] = {"conv1": conv(3, cin, cout),
                             "conv2": conv(3, cout, cout)}
        if down or cin != cout:
            disc[f"block{i}"]["conv_sc"] = conv(1, cin, cout)
    return {"gen": gen, "disc": disc}


def _is_shape(x):
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def _flat_shapes(config):
    """[(path string, shape)] in `jax.tree.flatten` order, and the tree
    structure."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes(config), is_leaf=_is_shape)
    return [("/".join(p.key for p in path), s) for path, s in flat], treedef


def init_params(config: dict, key):
    """BigGAN weights from the key: kernels, linears and embeddings
    orthogonal (the script's `--G_init ortho --D_init ortho`), each from
    its cut of one normal draw from the key over all of them in
    `jax.tree.flatten` order; the attention gammas, leaf i of that order,
    U(0.1, 0.5) from `fold_in(key, i)`; the output BatchNorm's gain 1,
    every other bias 0."""
    flat, treedef = _flat_shapes(config)
    return jax.tree.unflatten(treedef, _initializer(tuple(flat))(key))


def _orthogonal(a, shape):
    """`jax.nn.initializers.orthogonal` from its normal values `a`: Q of
    the QR of the taller (rows, outputs) matrix they fill, each column's
    sign that of R's diagonal, transposed back if it was wide."""
    rows, cols = math.prod(shape[:-1]), shape[-1]
    q, r = jnp.linalg.qr(a.reshape(max(rows, cols), min(rows, cols)))
    q = q * jnp.sign(jnp.diag(r))
    return (q.T if rows < cols else q).reshape(shape)


@functools.lru_cache(maxsize=None)
def _initializer(flat):
    """The initializer of the leaves `flat` [(path, shape)], jitted once:
    run op by op, each QR and draw would compile on its own."""
    def init(key):
        normal = jax.random.normal(key, (sum(math.prod(s) for _, s in flat
                                            if len(s) >= 2),))
        leaves, off = [], 0
        for i, (path, shape) in enumerate(flat):
            if len(shape) >= 2:
                n = math.prod(shape)
                leaves.append(_orthogonal(normal[off:off + n], shape))
                off += n
            elif not shape:
                leaves.append(jax.random.uniform(
                    jax.random.fold_in(key, i), (), jnp.float32, 0.1, 0.5))
            elif path.endswith("gain"):
                leaves.append(jnp.ones(shape, jnp.float32))
            else:
                leaves.append(jnp.zeros(shape, jnp.float32))
        return leaves

    return jax.jit(init)


def _normalized(path: str, shape) -> bool:
    """Every weight of two or more axes but G's shared embedding."""
    return len(shape) >= 2 and not path.endswith("shared")


def _w_mat(path: str, w):
    """BigGAN-PyTorch's W_mat, (outputs, inputs): a kernel's or linear's
    last axis are its outputs; the projection embedding's rows are (its
    `SNEmbedding` normalizes weight.view(num_embeddings, -1))."""
    if path.endswith("proj"):
        return w
    return w.reshape(-1, w.shape[-1]).T


def init_field_state(config: dict, key) -> dict:
    """{path: u} for every normalized weight: u over the weight's outputs,
    cut in `jax.tree.flatten` order of the normalized weights from one
    normal draw from `key(U0_SEED)`. The same for every seed."""
    del key
    outs = [(path, shape[0] if path.endswith("proj") else shape[-1])
            for path, shape in _flat_shapes(config)[0]
            if _normalized(path, shape)]
    normal = jax.random.normal(jax.random.key(U0_SEED),
                               (sum(n for _, n in outs),))
    out, off = {}, 0
    for path, n in outs:
        out[path] = normal[off:off + n]
        off += n
    return out


def draw(config: dict, key, rows: int):
    """The field's noise for `rows` fakes: z ~ N(0, I) of `dim_z` and the
    fakes' classes, uniform, from the two halves of the key."""
    bc = config["biggan_config"]
    kz, ky = jax.random.split(key)
    return (jax.random.normal(kz, (rows, bc["dim_z"])),
            jax.random.randint(ky, (rows,), 0, bc["n_classes"]))


# --------------------------------------------------------------------------- #
# reference
# --------------------------------------------------------------------------- #
def _l2normalize(x, eps):
    return x / jnp.maximum(jnp.linalg.norm(x), eps)


def power_iteration(params, state, eps, prec, control):
    """One power iteration per normalized weight at `params` from u:
    v = normalize(u W_mat), u' = normalize(v W_mat^T), both without
    gradient. Returns ({path: (u', v)}, the new state {path: u'})."""
    vecs = {}
    for p, w in jax.tree_util.tree_flatten_with_path(params)[0]:
        path = "/".join(k.key for k in p)
        if path not in state:
            continue
        m = operand(_w_mat(path, w), control)
        v = _l2normalize(jnp.dot(operand(state[path], control), m,
                                 precision=prec), eps)
        u = _l2normalize(jnp.dot(operand(v, control), m.T, precision=prec),
                         eps)
        vecs[path] = (lax.stop_gradient(u), lax.stop_gradient(v))
    return vecs, {k: u.astype(state[k].dtype)
                  for k, (u, _) in vecs.items()}


def sn_divide(tree, prefix, vecs, prec, control):
    """`tree` (G's or D's parameters) with each normalized weight W divided
    by sigma = v W_mat^T u', which keeps its gradient through W."""
    def one(p, w):
        path = "/".join([prefix] + [k.key for k in p])
        if path not in vecs:
            return w
        u, v = vecs[path]
        m = operand(_w_mat(path, w), control)
        sigma = jnp.dot(jnp.dot(operand(v, control), m.T, precision=prec),
                        operand(u, control), precision=prec)
        return w / sigma
    return jax.tree_util.tree_map_with_path(one, tree)


def _conv(p, x, prec, control, bias=True):
    y = lax.conv_general_dilated(operand(x, control), operand(p["w"] if bias
                                                              else p, control),
                                 (1, 1), "SAME", dimension_numbers=DN,
                                 precision=prec)
    return y + operand(p["b"], control) if bias else y


def _linear(x, w, prec, control):
    return jnp.dot(operand(x, control), operand(w, control), precision=prec)


def _bn(x, eps):
    """BatchNorm on the batch's own statistics, biased variance."""
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) / jnp.sqrt(var + eps)


def _ccbn(p, x, y, eps, prec, control):
    gain = 1.0 + _linear(y, p["gain"], prec, control)
    bias = _linear(y, p["bias"], prec, control)
    return _bn(x, eps) * gain[:, None, None, :] + bias[:, None, None, :]


def _pool2(x, how):
    b, h, w, c = x.shape
    return how(x.reshape(b, h // 2, 2, w // 2, 2, c), axis=(2, 4))


def _up2(x):
    return jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)


def attention(p, x, prec, control):
    """SAGAN's self-attention (BigGAN-PyTorch `Attention`): 1x1 convs
    theta, phi (to C/8) and g (to C/2), phi and g max-pooled 2x2, beta =
    softmax over keys of theta phi^T, o = 1x1 conv of beta g back to C,
    x + gamma o."""
    b, h, w, c = x.shape
    theta = _conv(p["theta"], x, prec, control, bias=False)
    phi = _pool2(_conv(p["phi"], x, prec, control, bias=False), jnp.max)
    g = _pool2(_conv(p["g"], x, prec, control, bias=False), jnp.max)
    theta = theta.reshape(b, h * w, c // 8)
    phi = phi.reshape(b, h * w // 4, c // 8)
    g = g.reshape(b, h * w // 4, c // 2)
    beta = jax.nn.softmax(jnp.einsum("bqc,bkc->bqk", operand(theta, control),
                                     operand(phi, control), precision=prec),
                          axis=-1)
    o = jnp.einsum("bqk,bkc->bqc", operand(beta, control),
                   operand(g, control), precision=prec).reshape(b, h, w,
                                                                c // 2)
    return x + operand(p["gamma"], control) * _conv(p["o"], o, prec, control,
                                                    bias=False)


def generate(gen, bc, z, c, prec, control):
    eps = bc["BN_eps"]
    gi, _, _, _ = _attn_at(bc)
    zs = jnp.split(z, len(G_MULTS) + 1, axis=1)
    shared = gen["shared"][c]
    h = _linear(zs[0], gen["linear"]["w"], prec, control) \
        + operand(gen["linear"]["b"], control)
    h = h.reshape(z.shape[0], BOTTOM, BOTTOM, -1)
    for i in range(len(G_MULTS)):
        p = gen[f"block{i}"]
        y = jnp.concatenate([shared, zs[i + 1]], axis=1)
        x = h
        h = _up2(jax.nn.relu(_ccbn(p["bn1"], x, y, eps, prec, control)))
        h = _conv(p["conv1"], h, prec, control)
        h = jax.nn.relu(_ccbn(p["bn2"], h, y, eps, prec, control))
        h = _conv(p["conv2"], h, prec, control) \
            + _conv(p["conv_sc"], _up2(x), prec, control)
        if i == gi:
            h = attention(gen["attn"], h, prec, control)
    fin = gen["final"]
    h = _bn(h, eps) * fin["bn"]["gain"] + fin["bn"]["bias"]
    return jnp.tanh(_conv(fin["conv"], jax.nn.relu(h), prec, control))


def discriminate(disc, bc, x, c, prec, control):
    _, _, di, _ = _attn_at(bc)
    h = x
    for i, (_, _, down, _) in enumerate(_blocks(bc)[1]):
        p = disc[f"block{i}"]
        x = h
        h = _conv(p["conv1"], jax.nn.relu(x) if i > 0 else x, prec, control)
        h = _conv(p["conv2"], jax.nn.relu(h), prec, control)
        if down:
            h = _pool2(h, jnp.mean)
        sc = x
        if i > 0:            # pre-activation: conv, then downsample
            sc = _conv(p["conv_sc"], sc, prec, control) if "conv_sc" in p \
                else sc
            sc = _pool2(sc, jnp.mean) if down else sc
        else:                # first block: downsample, then conv
            sc = _pool2(sc, jnp.mean) if down else sc
            sc = _conv(p["conv_sc"], sc, prec, control)
        h = h + sc
        if i == di:
            h = attention(disc["attn"], h, prec, control)
    h = jnp.sum(jax.nn.relu(h), axis=(1, 2))
    out = _linear(h, disc["linear"]["w"], prec, control)[:, 0] \
        + operand(disc["linear"]["b"], control)[0]
    return out + jnp.sum(operand(disc["proj"], control)[c]
                         * operand(h, control), axis=1)


def field(params, state, config, batch, noise, prec, control):
    """(field, loss = L_D + L_G, mean |D(real)|, new state)."""
    bc = config["biggan_config"]
    real, c_real = batch["real"], batch["label"]
    z, c_fake = noise
    vecs, new_state = power_iteration(params, state, bc["SN_eps"], prec,
                                      control)

    def gen_of(gen):
        return sn_divide(gen, "gen", vecs, prec, control)

    def disc_of(disc):
        return sn_divide(disc, "disc", vecs, prec, control)

    def loss_g(gen):
        fake = generate(gen_of(gen), bc, z, c_fake, prec, control)
        d = discriminate(disc_of(params["disc"]), bc, fake, c_fake, prec,
                         control)
        return -jnp.mean(d.astype(jnp.float32))

    def loss_d(disc):
        fake = lax.stop_gradient(generate(gen_of(params["gen"]), bc, z,
                                          c_fake, prec, control))
        d_real = discriminate(disc_of(disc), bc, real, c_real, prec,
                              control).astype(jnp.float32)
        d_fake = discriminate(disc_of(disc), bc, fake, c_fake, prec,
                              control).astype(jnp.float32)
        return (jnp.mean(jax.nn.relu(1.0 - d_real))
                + jnp.mean(jax.nn.relu(1.0 + d_fake)),
                jnp.mean(jnp.abs(d_real)))

    lg, g_gen = jax.value_and_grad(loss_g)(params["gen"])
    (ld, scale), g_disc = jax.value_and_grad(loss_d, has_aux=True)(
        params["disc"])
    mult = bc["disc_grad_mult"]
    grads = {"gen": g_gen,
             "disc": jax.tree.map(lambda x: mult * x, g_disc)}
    grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads, params)
    return grads, ld + lg, scale, new_state


# --------------------------------------------------------------------------- #
# model FLOPs and parameter sizes
# --------------------------------------------------------------------------- #
def taps(n: int, k: int) -> int:
    """Products along one axis of a stride-1 SAME convolution of odd size
    `k` over length `n` that land inside the input."""
    r = k // 2
    return n * k - 2 * sum(r - i for i in range(r))


def _conv_macs(res, k, cin, cout):
    return taps(res, k) ** 2 * cin * cout


def _attn_products(bc, c, res):
    """Per image: [(MACs, both operands activations)] of the attention at
    width `c` on `res` x `res` maps."""
    hw = res * res
    return [(_conv_macs(res, 1, c, c // 8), False),            # theta
            (_conv_macs(res, 1, c, c // 8), False),            # phi
            (_conv_macs(res, 1, c, c // 2), False),            # g
            (hw * (hw // 4) * (c // 8), True),                 # theta phi^T
            (hw * (hw // 4) * (c // 2), True),                 # beta g
            (_conv_macs(res, 1, c // 2, c), False)]            # o


def layer_macs(config: dict):
    """Per image, in forward order: G's products [(MACs, both operands
    activations, reads z)] and D's [(MACs, both operands activations,
    reads the image)]."""
    bc = config["biggan_config"]
    g, d = _blocks(bc)
    gi, g_attn, di, d_attn = _attn_at(bc)
    y_dim = bc["shared_dim"] + _z_chunk(bc)
    gen = [(_z_chunk(bc) * 16 * bc["G_ch"] * BOTTOM * BOTTOM, False, True)]
    for i, (cin, cout, res) in enumerate(g):
        gen += [(2 * y_dim * cin, False, False),                  # ccbn1
                (_conv_macs(res, 3, cin, cout), False, False),    # conv1
                (2 * y_dim * cout, False, False),                 # ccbn2
                (_conv_macs(res, 3, cout, cout), False, False),   # conv2
                (_conv_macs(res, 1, cin, cout), False, False)]    # conv_sc
        if i == gi:
            gen += [(m, a, False) for m, a in _attn_products(bc, g_attn, res)]
    gen.append((_conv_macs(RESOLUTION, 3, bc["G_ch"], CHANNELS), False,
                False))
    disc, res = [], RESOLUTION
    for i, (cin, cout, down, out_res) in enumerate(d):
        disc += [(_conv_macs(res, 3, cin, cout), False, i == 0),
                 (_conv_macs(res, 3, cout, cout), False, False)]
        if down or cin != cout:   # the first block pools before its conv
            sc_res = out_res if i == 0 else res
            disc.append((_conv_macs(sc_res, 1, cin, cout), False, i == 0))
        res = out_res
        if i == di:
            disc += [(m, a, False) for m, a in _attn_products(bc, d_attn,
                                                              res)]
    disc += [(d[-1][1], False, False),      # linear
             (d[-1][1], False, False)]      # class projection
    return gen, disc


def sn_macs(config: dict) -> int:
    """Multiply-accumulates of one worker's power iterations and sigmas:
    three matrix-vector products per normalized weight."""
    return sum(3 * math.prod(s) for p, s in _flat_shapes(config)[0]
               if _normalized(p, s))


def field_flops_per_image(config: dict) -> int:
    """Model FLOPs of the hinge field for one image, spectral norm aside
    (see the module doc)."""
    gen, disc = layer_macs(config)
    fwd = sum(m for m, _, _ in gen) + 2 * sum(m for m, _, _ in disc)
    # a weight-gradient pass: dW of every product and dX of every product
    # that does not read the input; a product of two activations needs both
    g_bwd = sum(2 * m if a or not first else m for m, a, first in gen)
    d_dw = sum(2 * m if a or not first else m for m, a, first in disc)
    d_dx = sum(2 * m if a else m for m, a, _ in disc)
    macs = fwd + g_bwd + 2 * d_dw + d_dx
    return 2 * macs


def step_flops(config: dict, batch_per_worker: int, workers: int) -> int:
    """Model FLOPs of one training step over all workers."""
    return workers * (field_flops_per_image(config) * batch_per_worker
                      + 2 * sn_macs(config))


def param_sizes(config: dict):
    """Element counts of the parameter leaves in `jax.tree.flatten`
    order."""
    return [math.prod(s) for _, s in _flat_shapes(config)[0]]


def n_params(config: dict) -> int:
    return sum(param_sizes(config))
