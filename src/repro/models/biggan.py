"""BigGAN (Brock et al., arXiv:1809.11096) at 128x128, as BigGAN-PyTorch
builds it (`BigGAN.py`, `layers.py`; `launch_BigGAN_bs256x8.sh`).

Generator: z (dim_z, split hierarchically into one chunk per block and
one for the input linear) and a class y, embedded by a shared table
(`shared`, no spectral norm). Each block's class-conditional BatchNorm
(ccbn) reads [shared(y), z_chunk]:

    h = linear(z_0) -> (B, 4, 4, 16ch)
    GBlock:  relu(ccbn1(x)) -> up2 -> conv3x3 -> relu(ccbn2) -> conv3x3
             + conv1x1(up2(x))                  (nearest upsampling)
    ccbn:    BN(x; the batch's own statistics) * (1 + W_g y) + W_b y
    widths 16ch -> 16 -> 8 -> 4 -> 2 -> 1ch, self-attention after the
    block whose output is G_attn wide; then BN, relu, conv3x3 to RGB, tanh.

Discriminator: residual down-blocks (the first without pre-activation),
self-attention after the block at D_attn, relu, a sum over space, a linear
and the projection <proj(y), h>:

    DBlock:  conv3x3 -> relu -> conv3x3 -> avgpool2 + shortcut
    widths 3 -> 1 -> 2 -> 4 -> 8 -> 16 -> 16ch (the last block keeps 4x4).

Self-attention (SAGAN): theta, phi to C/8 and g to C/2 by 1x1 convs, phi
and g max-pooled 2x2, softmax(theta phi^T) g, a 1x1 conv back to C,
scaled by the learned scalar gamma and added to the input.

Spectral norm on every weight but `shared` (BigGAN-PyTorch's
`power_iteration`): one power iteration per field evaluation from the
persisted vector u, W_mat the weight with its output units as rows,

    v = normalize(u W_mat),  u' = normalize(v W_mat^T),  sigma = v W_mat^T u'

u and v take no gradient, sigma keeps its gradient through W, and the
layer uses W / sigma. The vectors u are the field's state: one per
normalized weight, carried from step to step (`init_sn_state`,
`spectral_norm`).

Weights are made from the seed by a rule of their position: matrices and
kernels are orthogonal (the script's `--G_init ortho --D_init ortho`),
each the Q of a QR of its cut of one normal draw from the key over all of
them in `jax.tree.flatten` order (`orthogonal`); the attention gammas,
leaf i of that order, U(0.1, 0.5) from `fold_in(key, i)` (the published
init is 0; nonzero lets the first steps see the attention path); biases
0, the output BatchNorm's gain 1. The vectors u start as cuts of one
normal draw from `key(U0_SEED)`, in the order of the normalized weights,
so that they follow from the parameters' shapes alone. One draw each
keeps the seeded init to two random-number kernels to compile.

Ops of the attention run under the named scope `repro.obs/attention`, of
each power iteration and the division by sigma under
`repro.obs/spectral_norm`.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

U0_SEED = 0
RESOLUTION = 128     # the architecture below is BigGAN's 128x128 one
CHANNELS = 3
BOTTOM_WIDTH = 4     # G's first feature map is 4x4
DN = ("NHWC", "HWIO", "NHWC")
ATTN_SCOPE = "repro.obs/attention"
SN_SCOPE = "repro.obs/spectral_norm"


@dataclass(frozen=True)
class BigGANConfig:
    name: str = "biggan128"
    arch_type: str = "gan"
    G_ch: int = 96
    D_ch: int = 96
    dim_z: int = 120
    shared_dim: int = 128
    n_classes: int = 1000
    G_attn: int = 64
    D_attn: int = 64
    SN_eps: float = 1e-6
    BN_eps: float = 1e-5
    # D_lr / G_lr of the script (4e-4 / 1e-4): the simultaneous field's
    # stand-in for the two learning rates
    disc_grad_mult: float = 4.0

    @property
    def image_size(self) -> int:
        return RESOLUTION

    @property
    def channels(self) -> int:
        return CHANNELS

    @property
    def z_chunk(self) -> int:
        return self.dim_z // (len(G_MULTS) + 1)

    def reduced(self) -> "BigGANConfig":
        """Every kind of layer at 128x128, narrow: ch 8 (the narrowest at
        which D's attention keeps a query width of 1), 10 classes, a
        shared embedding of 16."""
        return dataclasses.replace(self, name=self.name + "-smoke", G_ch=8,
                                   D_ch=8, n_classes=10, shared_dim=16)


# (in, out) width multiples of ch, and the output resolution, per block
G_MULTS = ((16, 16), (16, 8), (8, 4), (4, 2), (2, 1))
D_MULTS = ((0, 1), (1, 2), (2, 4), (4, 8), (8, 16), (16, 16))   # 0: RGB


def g_blocks(cfg):
    """[(in, out, output resolution)] of G's blocks."""
    res = BOTTOM_WIDTH
    out = []
    for a, b in G_MULTS:
        res *= 2
        out.append((a * cfg.G_ch, b * cfg.G_ch, res))
    return out


def d_blocks(cfg):
    """[(in, out, downsample, output resolution)] of D's blocks."""
    res = RESOLUTION
    out = []
    for i, (a, b) in enumerate(D_MULTS):
        down = i < len(D_MULTS) - 1
        res = res // 2 if down else res
        out.append((a * cfg.D_ch if a else CHANNELS, b * cfg.D_ch, down, res))
    return out


def g_attn_block(cfg):
    return next(i for i, (_, _, r) in enumerate(g_blocks(cfg))
                if r == cfg.G_attn)


def d_attn_block(cfg):
    return next(i for i, (_, _, _, r) in enumerate(d_blocks(cfg))
                if r == cfg.D_attn)


# --------------------------------------------------------------------------- #
# parameters
# --------------------------------------------------------------------------- #
def _conv(k, cin, cout):
    return {"w": (k, k, cin, cout), "b": (cout,)}


def _attn(c):
    return {"theta": (1, 1, c, c // 8), "phi": (1, 1, c, c // 8),
            "g": (1, 1, c, c // 2), "o": (1, 1, c // 2, c), "gamma": ()}


def param_shapes(cfg: BigGANConfig):
    """The parameter tree's shapes (tuples)."""
    y_dim = cfg.shared_dim + cfg.z_chunk
    gen = {"shared": (cfg.n_classes, cfg.shared_dim),
           "linear": {"w": (cfg.z_chunk, 16 * cfg.G_ch * BOTTOM_WIDTH ** 2),
                      "b": (16 * cfg.G_ch * BOTTOM_WIDTH ** 2,)}}
    for i, (cin, cout, _) in enumerate(g_blocks(cfg)):
        gen[f"block{i}"] = {
            "bn1": {"gain": (y_dim, cin), "bias": (y_dim, cin)},
            "conv1": _conv(3, cin, cout),
            "bn2": {"gain": (y_dim, cout), "bias": (y_dim, cout)},
            "conv2": _conv(3, cout, cout),
            "conv_sc": _conv(1, cin, cout)}
    gen["attn"] = _attn(g_blocks(cfg)[g_attn_block(cfg)][1])
    gen["final"] = {"bn": {"gain": (cfg.G_ch,), "bias": (cfg.G_ch,)},
                    "conv": _conv(3, cfg.G_ch, CHANNELS)}
    disc = {}
    for i, (cin, cout, down, _) in enumerate(d_blocks(cfg)):
        blk = {"conv1": _conv(3, cin, cout), "conv2": _conv(3, cout, cout)}
        if down or cin != cout:
            blk["conv_sc"] = _conv(1, cin, cout)
        disc[f"block{i}"] = blk
    c_top = d_blocks(cfg)[-1][1]
    disc["attn"] = _attn(d_blocks(cfg)[d_attn_block(cfg)][1])
    disc["linear"] = {"w": (c_top, 1), "b": (1,)}
    disc["proj"] = (cfg.n_classes, c_top)
    return {"gen": gen, "disc": disc}


def _is_shape(x):
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def orthogonal(a, shape):
    """The orthogonal matrix of `jax.nn.initializers.orthogonal` made from
    the normal values `a` (as many as `shape` holds), outputs on the last
    axis: Q of the QR of the taller way of holding them, its columns' signs
    set by R's diagonal."""
    rows, cols = math.prod(shape[:-1]), shape[-1]
    q, r = jnp.linalg.qr(a.reshape(max(rows, cols), min(rows, cols)))
    q = q * jnp.sign(jnp.diag(r))
    return (q.T if rows < cols else q).reshape(shape)


def init(key, cfg: BigGANConfig):
    """Parameters from the key (see the module doc for the rule)."""
    shapes = jax.tree_util.tree_flatten_with_path(param_shapes(cfg),
                                                  is_leaf=_is_shape)
    sizes = [math.prod(s) for _, s in shapes[0] if len(s) >= 2]
    normal = jax.random.normal(key, (sum(sizes),))
    leaves, off = [], 0
    for i, (path, shape) in enumerate(shapes[0]):
        if len(shape) >= 2:
            n = math.prod(shape)
            leaves.append(orthogonal(normal[off:off + n], shape))
            off += n
        elif len(shape) == 0:
            leaves.append(jax.random.uniform(jax.random.fold_in(key, i), (),
                                             jnp.float32, 0.1, 0.5))
        elif path[-1].key == "gain":
            leaves.append(jnp.ones(shape, jnp.float32))
        else:
            leaves.append(jnp.zeros(shape, jnp.float32))
    return jax.tree.unflatten(shapes[1], leaves)


# --------------------------------------------------------------------------- #
# spectral norm
# --------------------------------------------------------------------------- #
def _path_str(path) -> str:
    return "/".join(str(p.key) for p in path)


def sn_leaves(params):
    """[(path string, leaf)] of the spectrally normalized weights, in
    `jax.tree.flatten` order: every leaf of two or more axes but G's
    shared class embedding."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    return [(_path_str(p), x) for p, x in flat
            if x.ndim >= 2 and p[-1].key != "shared"]


def _sn_matrix(path: str, w):
    """The weight as (inputs, outputs): W_mat transposed. A kernel or
    linear keeps its last axis as outputs; the projection embedding's
    rows are its outputs, as in BigGAN-PyTorch's SNEmbedding."""
    if path.endswith("proj"):
        return w.T
    return w.reshape(-1, w.shape[-1])


def init_sn_state(params):
    """{path: u} for every normalized weight, u a normal vector over the
    weight's outputs, cut in `sn_leaves` order from one normal draw from
    `key(U0_SEED)`."""
    outs = [(path, _sn_matrix(path, w).shape[1])
            for path, w in sn_leaves(params)]
    normal = jax.random.normal(jax.random.key(U0_SEED),
                               (sum(n for _, n in outs),))
    state, off = {}, 0
    for path, n in outs:
        state[path] = normal[off:off + n]
        off += n
    return state


def _normalize(x, eps):
    return x / jnp.maximum(jnp.linalg.norm(x), eps)


def spectral_norm(params, state, eps):
    """(params with each normalized weight divided by its sigma, the new
    state {path: u'}): one power iteration per weight from `state`."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out, new_state = [], {}
    for p, w in flat:
        path = _path_str(p)
        if path not in state:
            out.append(w)
            continue
        with jax.named_scope(SN_SCOPE):
            m = _sn_matrix(path, w)
            v = _normalize(m @ state[path], eps)
            u = _normalize(v @ m, eps)
            v, u = lax.stop_gradient(v), lax.stop_gradient(u)
            sigma = v @ (m @ u)
            out.append(w / sigma)
        new_state[path] = u
    return jax.tree.unflatten(treedef, out), new_state


# --------------------------------------------------------------------------- #
# layers (NHWC)
# --------------------------------------------------------------------------- #
def conv(p, x):
    return lax.conv_general_dilated(x, p["w"], (1, 1), "SAME",
                                    dimension_numbers=DN) + p["b"]


def _conv1x1(w, x):
    return lax.conv_general_dilated(x, w, (1, 1), "VALID",
                                    dimension_numbers=DN)


def upsample(x):
    return jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)


def avgpool(x):
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).mean(axis=(2, 4))


def maxpool(x):
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def batch_norm(x, eps):
    """Normalized by the batch's own mean and (biased) variance."""
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + eps)


def ccbn(p, x, y, eps):
    gain = 1.0 + y @ p["gain"]
    bias = y @ p["bias"]
    return batch_norm(x, eps) * gain[:, None, None, :] + bias[:, None, None, :]


def attention(p, x):
    with jax.named_scope(ATTN_SCOPE):
        b, h, w, c = x.shape
        theta = _conv1x1(p["theta"], x).reshape(b, h * w, -1)
        phi = maxpool(_conv1x1(p["phi"], x)).reshape(b, h * w // 4, -1)
        g = maxpool(_conv1x1(p["g"], x)).reshape(b, h * w // 4, -1)
        beta = jax.nn.softmax(jnp.einsum("bqc,bkc->bqk", theta, phi), -1)
        o = jnp.einsum("bqk,bkc->bqc", beta, g).reshape(b, h, w, c // 2)
        return p["gamma"] * _conv1x1(p["o"], o) + x


def g_block(p, x, y, eps):
    h = upsample(jax.nn.relu(ccbn(p["bn1"], x, y, eps)))
    h = conv(p["conv1"], h)
    h = conv(p["conv2"], jax.nn.relu(ccbn(p["bn2"], h, y, eps)))
    return h + conv(p["conv_sc"], upsample(x))


def d_block(p, x, preact, down):
    h = conv(p["conv1"], jax.nn.relu(x) if preact else x)
    h = conv(p["conv2"], jax.nn.relu(h))
    sc = x
    if preact:
        sc = conv(p["conv_sc"], sc) if "conv_sc" in p else sc
        sc = avgpool(sc) if down else sc
    else:
        sc = avgpool(sc) if down else sc
        sc = conv(p["conv_sc"], sc) if "conv_sc" in p else sc
    return (avgpool(h) if down else h) + sc


# --------------------------------------------------------------------------- #
# G and D (on spectrally normalized parameters)
# --------------------------------------------------------------------------- #
def draw(cfg: BigGANConfig, rng, rows: int):
    """G's noise for `rows` fakes: z ~ N(0, I) and classes uniform."""
    kz, ky = jax.random.split(rng)
    return (jax.random.normal(kz, (rows, cfg.dim_z)),
            jax.random.randint(ky, (rows,), 0, cfg.n_classes))


def generate(gen, cfg: BigGANConfig, z, y):
    eps = cfg.BN_eps
    zs = jnp.split(z, len(G_MULTS) + 1, axis=1)
    shared = gen["shared"][y]
    h = (zs[0] @ gen["linear"]["w"] + gen["linear"]["b"]).reshape(
        z.shape[0], BOTTOM_WIDTH, BOTTOM_WIDTH, -1)
    for i in range(len(G_MULTS)):
        h = g_block(gen[f"block{i}"], h,
                    jnp.concatenate([shared, zs[i + 1]], axis=1), eps)
        if i == g_attn_block(cfg):
            h = attention(gen["attn"], h)
    fin = gen["final"]
    h = batch_norm(h, eps) * fin["bn"]["gain"] + fin["bn"]["bias"]
    return jnp.tanh(conv(fin["conv"], jax.nn.relu(h)))


def discriminate(disc, cfg: BigGANConfig, x, y):
    h = x
    for i, (_, _, down, _) in enumerate(d_blocks(cfg)):
        h = d_block(disc[f"block{i}"], h, preact=i > 0, down=down)
        if i == d_attn_block(cfg):
            h = attention(disc["attn"], h)
    h = jnp.sum(jax.nn.relu(h), axis=(1, 2))
    out = (h @ disc["linear"]["w"] + disc["linear"]["b"])[:, 0]
    return out + jnp.sum(disc["proj"][y] * h, axis=1)
