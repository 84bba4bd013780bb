"""Plain float32 reference of the timed training step (Algorithm 2).

Written from the algorithm, not from the program, in plain `jax.numpy` and
`jax.lax`, at the matmul precision the configuration states:

  worker m:  w_half = w - (lr * g_prev^m + e1^m)          (OMD lookahead)
             g^m    = F(w_half; batch^m, noise^m)          (the field)
             p^m    = lr * g^m                             (the update message)
  exchange:  q = mean_m Q(p^m + e1^m), e1^m the residual   (error feedback)
             with two_phase: each worker's chunk of the mean is quantized
             again with the owner's residual e2 before it is gathered
  all:       w = w - q

F, its noise and the weights belong to the configuration's family
(`bench/families/<family>.py`): `init_params`, `draw` (the noise of a
worker's rows, such as a GAN's latent z), and `field`, which may carry a
state of the family's own per worker that is neither trained nor
exchanged (`init_field_state`). This module knows no model. Q is stochastic
int8 quantization with one linf scale per 1024 elements of the flat comm
bucket; the fused kernel's rounding floor(m/s*127) + [u < frac] on the
worker side of one chip and on the owner side of two_phase, QSGD's
sign-magnitude rounding on the worker side of two_phase. The exact exchange
is a plain mean with no quantizer and no residual.

The configurations state float32 at the default matmul precision
(`matmul_precision` in the configuration file), which on a TPU multiplies
each convolution's and product's operands in one bfloat16 pass and
accumulates in float32, forward and backward; everything else is float32.

The reference draws what the algorithm draws from the seed as the algorithm
defines it: the weights, the noise of each worker and step, and the
uniforms of each bucket's stochastic rounding. It takes no array from the
program. Both start from the same optimistic term g_prev, which the
algorithm leaves free (the program's own start is 0): `lookahead_probe`
draws it from the seed, so that step 1's lookahead point lies a tenth of
each leaf's size away from the weights and a step that drops or misapplies
the lookahead reads apart from one that keeps it. At the mixes' lr (1e-6)
the lookahead moves the weights by about lr * g from step 2 on, which no
comparison of three steps can tell from no lookahead at all.

`control` computes the field in a lower precision: "bfloat16", the step
below float32 at the default precision (products, activations and their
cotangents in bfloat16), or "float8_e4m3fn" (the operands of the forward
products rounded to float8 e4m3); families apply it to each product's
operands with `operand`. `fault` plants one of the faults the comparison
must catch (`FAULTS`).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import flops
from pool import seed_key

LEVELS = 127
BLOCK = 1024
PROBE = 0.1          # step 1's lookahead displacement, over each leaf's rms
PROBE_TAG = 0x0A4EAD

FAULTS = (
    "half_batch",    # half of each worker's rows and noise, the mean over them
    "no_exchange",   # each worker applies its own update (several workers)
    "no_lookahead",  # the field at w, not at the OMD lookahead point
    "sign_flip",     # the apply adds the exchanged update: w + q
)


# --------------------------------------------------------------------------- #
# precision
# --------------------------------------------------------------------------- #
PRECISION = {"default": lax.Precision.DEFAULT,
             "highest": lax.Precision.HIGHEST}


def operand(x, control):
    """A forward convolution's or product's operand: as it is; for the
    bfloat16 control cast, so that the field's products, activations and
    their cotangents are bfloat16; for the float8 control rounded to e4m3
    and kept float32 (straight through: the cotangent stays float32)."""
    if control is None:
        return x
    if control == "bfloat16":
        return x.astype(jnp.bfloat16)
    return x + lax.stop_gradient(
        x.astype(jnp.float8_e4m3fn).astype(jnp.float32) - x)


# --------------------------------------------------------------------------- #
# quantizers and the exchange
# --------------------------------------------------------------------------- #
def quantize_fused(m, u):
    """The fused kernel's rounding of a flat f32 array, one scale per 1024
    elements. Returns (dequantized, residual)."""
    mb = m.reshape(-1, BLOCK)
    s = jnp.max(jnp.abs(mb), axis=1, keepdims=True) + 1e-20
    lv = mb / s * LEVELS
    low = jnp.floor(lv)
    q = low + (u.reshape(mb.shape) < (lv - low)).astype(jnp.float32)
    deq = q * (s / LEVELS)
    return deq.reshape(m.shape), (mb - deq).reshape(m.shape)


def quantize_qsgd(v, key):
    """QSGD's sign-magnitude stochastic rounding, one linf scale per 1024
    elements (the compressor qsgd8_block1024). Returns the dequantized."""
    fb = v.reshape(-1, BLOCK)
    s = jnp.max(jnp.abs(fb), axis=1, keepdims=True) + 1e-20
    lv = jnp.abs(fb) / s * LEVELS
    low = jnp.floor(lv)
    q = low + (jax.random.uniform(key, fb.shape) < (lv - low)).astype(
        jnp.float32)
    return (jnp.sign(fb) * q * (s / LEVELS)).reshape(v.shape)


def _pack(leaves, members, size):
    """Leaves (each with a leading worker axis) of one bucket, flattened in
    order and zero-padded: (W, size)."""
    flat = jnp.concatenate([leaves[i].reshape(leaves[i].shape[0], -1)
                            for i in members], axis=1)
    return jnp.pad(flat, ((0, 0), (0, size - flat.shape[1])))


def _unpack_into(out, flat, members, shapes):
    """Scatter a bucket's (W, size) rows back over leaves (W, *shape)."""
    off = 0
    for i in members:
        n = math.prod(shapes[i])
        out[i] = flat[:, off:off + n].reshape((flat.shape[0],) + shapes[i])
        off += n


def exchange_compressed(msgs, e1, e2, kqs, layout, shapes, two_phase):
    """One compressed exchange over W workers. msgs, e1: leaves with a
    leading worker axis; e2: per bucket, (W, size / W) owner residuals;
    kqs: (W,) keys. Returns (q leaves, new e1 leaves, new e2)."""
    W = kqs.shape[0]
    q = [None] * len(shapes)
    new_e1 = [None] * len(shapes)
    new_e2 = list(e2)
    for bid, (size, members) in enumerate(layout):
        m = _pack(msgs, members, size) + _pack(e1, members, size)
        keys = jax.vmap(lambda k: jax.random.fold_in(k, 100_000 + bid))(kqs)
        if not two_phase:
            u = jax.vmap(lambda k: jax.random.uniform(k, (size,)))(keys)
            deq, res = jax.vmap(quantize_fused)(m, u)
            _unpack_into(q, deq[:1], members, shapes)
            _unpack_into(new_e1, res, members, shapes)
            continue
        c = size // W
        splits = jax.vmap(lambda k: jax.random.split(k, W + 1))(keys)
        x = m.reshape(W, W, c)                      # (worker, owner, chunk)
        x_hat = jax.vmap(jax.vmap(quantize_qsgd))(x, splits[:, :W])
        _unpack_into(new_e1, (x - x_hat).reshape(W, size), members, shapes)
        mean = jnp.mean(x_hat, axis=0)              # each owner's chunk
        u = jax.vmap(lambda k: jax.random.uniform(k, (c,)))(splits[:, W])
        deq, res = jax.vmap(quantize_fused)(mean + e2[bid], u)
        new_e2[bid] = res
        _unpack_into(q, deq.reshape(1, size), members, shapes)
    return [x[0] for x in q], new_e1, new_e2


# --------------------------------------------------------------------------- #
# the step
# --------------------------------------------------------------------------- #
def _rows(tree):
    return jax.tree.leaves(tree)[0].shape[0]


def make_step(fam, config, traffic, W, prec, control=None, fault=None):
    """step(state, batches, key, i) -> (state, loss, loss scale, the field
    averaged over workers, the applied update), the last two as flat leaf
    lists. `batches` is a batch dict whose arrays lead with (W, B); per-worker
    state leaves lead with the worker axis."""
    lr = traffic["lr"]
    if traffic["exchange"] not in ("single", "two_phase", "exact"):
        raise ValueError(f"no reference for exchange {traffic['exchange']!r}")
    compressed = traffic["exchange"] != "exact"
    two_phase = traffic["exchange"] == "two_phase"

    def worker(params, fstate, prev, e1, batch, kfield):
        half = jax.tree.map(lambda p, g, e: p - (lr * g + e), params, prev,
                            e1)
        if fault == "no_lookahead":
            half = params
        noise = fam.draw(config, kfield, _rows(batch))
        if fault == "half_batch":
            batch, noise = jax.tree.map(lambda x: x[: x.shape[0] // 2],
                                        (batch, noise))
        return fam.field(half, fstate, config, batch, noise, prec, control)

    def step(state, batches, key, i):
        params = state["params"]
        leaves_w, treedef = jax.tree.flatten(params)
        shapes = [tuple(x.shape) for x in leaves_w]
        layout = flops.bucket_layout([math.prod(s) for s in shapes], W)
        kws = (jax.vmap(lambda w: jax.random.fold_in(key, w))(jnp.arange(W))
               if W > 1 else key[None])
        kfield, kq = jax.vmap(
            lambda k: tuple(jax.random.split(jax.random.fold_in(k, i))))(kws)
        unflat = partial(jax.tree.unflatten, treedef)
        g, losses, scales, fstate = jax.vmap(
            worker, in_axes=(None, 0, 0, 0, 0, 0))(
            params, state["field"], unflat(state["prev_grad"]),
            unflat(state["e1"]), batches, kfield)
        g = jax.tree.leaves(g)
        msgs = [lr * x for x in g]
        e1, e2 = state["e1"], state["e2"]
        n_ex = 1 if fault == "no_exchange" else W
        if compressed:
            q, new_e1, new_e2 = exchange_compressed(
                [x[:n_ex] for x in msgs], [x[:n_ex] for x in e1],
                [x[:n_ex] for x in e2], kq[:n_ex], layout, shapes,
                two_phase and n_ex > 1)
            if n_ex < W:
                new_e1 = [jnp.concatenate([a, b[n_ex:]])
                          for a, b in zip(new_e1, e1)]
                new_e2 = [jnp.concatenate([a, b[n_ex:]])
                          for a, b in zip(new_e2, e2)]
        else:
            q = [jnp.mean(x[:n_ex], axis=0) for x in msgs]
            new_e1, new_e2 = e1, e2
        sign = 1.0 if fault == "sign_flip" else -1.0
        new_state = {"params": unflat([p + sign * u
                                       for p, u in zip(leaves_w, q)]),
                     "prev_grad": g, "e1": new_e1, "e2": new_e2,
                     "field": fstate}
        mean_field = [jnp.mean(x, axis=0) for x in g]
        return (new_state, jnp.mean(losses), jnp.mean(scales), mean_field,
                q)

    return step


def lookahead_probe(fam, config, seed, W, lr):
    """The optimistic term g_prev both sides start from: per flat leaf,
    (W, *shape), so that lr * g_prev is PROBE times the leaf's rms at init
    in a seeded normal direction of each worker's own (0 on the biases,
    which start at 0). Made on the device in one jitted call."""
    def make(key):
        w0 = jax.tree.leaves(fam.init_params(config, key))
        ks = jax.random.split(jax.random.fold_in(key, PROBE_TAG), len(w0))
        return [jax.random.normal(k, (W,) + x.shape)
                * (PROBE / lr * jnp.sqrt(jnp.mean(x * x)))
                for k, x in zip(ks, w0)]

    return jax.jit(make)(seed_key(seed))


def init_state(fam, config, key, W, prev_grad):
    params = fam.init_params(config, key)
    leaves = jax.tree.leaves(params)
    layout = flops.bucket_layout([x.size for x in leaves], W)
    zeros = [jnp.zeros((W,) + x.shape) for x in leaves]
    field = jax.tree.map(lambda x: jnp.broadcast_to(x, (W,) + x.shape),
                         fam.init_field_state(config, key))
    return {"params": params, "prev_grad": list(prev_grad),
            "e1": list(zeros),
            "e2": [jnp.zeros((W, size // W)) for size, _ in layout],
            "field": field}


def run_reference(fam, config, traffic, seed, batches, W, steps=3,
                  control=None, fault=None):
    """The first `steps` steps of the family `fam`'s model under the
    configuration `config` from the seed, on `batches` (a batch dict whose
    arrays lead with (steps, W, B)), at the matmul precision the
    configuration states, from the seeded optimistic term of
    `lookahead_probe`. Returns host arrays: losses, loss scales, the
    per-leaf norms of the first field (mean over workers), the first
    applied update, and the flat parameter leaves before the first step
    and after the last."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no fault {fault!r}; have {FAULTS}")
    key = seed_key(seed)
    step = jax.jit(make_step(fam, config, traffic, W,
                             PRECISION[config["matmul_precision"]], control,
                             fault))
    state = init_state(fam, config, key, W,
                       lookahead_probe(fam, config, seed, W, traffic["lr"]))
    w0 = jax.device_get(jax.tree.leaves(state["params"]))
    losses, scales, g1, q1 = [], [], None, None
    for i in range(steps):
        state, loss, scale, mean_field, q = step(
            state, jax.tree.map(lambda x: x[i], batches), key, jnp.int32(i))
        losses.append(float(loss))
        scales.append(float(scale))
        if i == 0:
            g1 = [float(np.linalg.norm(np.asarray(x, np.float64)))
                  for x in jax.device_get(mean_field)]
            q1 = jax.device_get(q)
    w3 = jax.device_get(jax.tree.leaves(state["params"]))
    return {"losses": losses, "scales": scales, "g1_norms": g1,
            "q1": [np.asarray(x) for x in q1],
            "w0": [np.asarray(x) for x in w0],
            "w3": [np.asarray(x) for x in w3]}
