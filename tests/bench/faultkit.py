"""Faults planted under the timed path, shared by the two fault tests.

Each cell (or waiting four-chip mix, see `benchkit.WAITING`) at smoke size,
with the chip check skipped and the rest of the run as the benchmark drives
it, once sound and once for each fault the cell can have: a step that
returns its state unchanged; half of each worker's batch left out with the
mean taken over the rest; the OMD lookahead's optimistic term dropped (the
field taken at w less the residual alone); the exchanged update applied
with its sign flipped (w + q); and on several chips the exchange between
them left out (each worker applies its own update). Each cell's own limits
decide.
"""
import json

import benchkit

FAULTS = r'''
import jax
import jax.numpy as jnp

def unchanged(prog):
    inner = jax.jit(prog.trainer.step, static_argnums=(3,))
    prog.step = lambda st, b, k, d: inner(st, b, k, d)._replace(state=st)

def half_batch(prog):
    orig, W = prog.step, prog.n_workers

    def half(x):
        B = x.shape[0] // W
        return x.reshape((W, B) + x.shape[1:])[:, :B // 2].reshape(
            (-1,) + x.shape[1:])

    prog.step = lambda st, b, k, d: orig(st, jax.tree.map(half, b), k, d)

def no_lookahead(prog):
    orig = prog.step
    prog.step = lambda st, b, k, d: orig(
        st._replace(prev_grad=jax.tree.map(jnp.zeros_like, st.prev_grad)),
        b, k, d)

def sign_flip(prog):
    tr = prog.trainer
    orig = type(tr)._server_update

    def server_update(state, qhat):
        new_params, *rest = orig(tr, state, qhat)
        flipped = jax.tree.map(lambda w, n: w + (w - n), state.params,
                               new_params)
        return (flipped, *rest)
    object.__setattr__(tr, "_server_update", server_update)

def no_exchange(prog):
    tr = prog.trainer
    orig = type(tr)._exchange_tree
    object.__setattr__(
        tr, "_exchange_tree",
        lambda m, ef, plans, key, axes, **kw: orig(tr, m, ef, plans, key,
                                                   (), **kw))

FAULTS = {"none": None, "unchanged": unchanged, "half_batch": half_batch,
          "no_lookahead": no_lookahead, "sign_flip": sign_flip,
          "no_exchange": no_exchange}
'''

CODE = FAULTS + r'''
import json, sys
import benchkit, reference, run

# every fault is planted in the program: the reference is the same for all
_ref, _sound = reference.run_reference, []


def _reference_once(*a, **kw):
    if not _sound:
        _sound.append(_ref(*a, **kw))
    return _sound[0]


reference.run_reference = _reference_once

cell, faults = sys.argv[1], sys.argv[2].split(",")
res = {}
for f in faults:
    spec = benchkit.smoke(benchkit.resolve(cell))
    out = run.run_cell(spec, benchkit.SEED, 0.2, False, run.device_info(),
                       step_fault=FAULTS[f])
    res[f] = out["correct"]
print(json.dumps(res))
'''

ONE_CHIP = ["none", "unchanged", "half_batch", "no_lookahead", "sign_flip"]
FOUR_CHIPS = ONE_CHIP + ["no_exchange"]


def check_faults(cell, chips):
    """`cell` (a manifest cell or a waiting mix) at smoke size."""
    faults = FOUR_CHIPS if chips == 4 else ONE_CHIP
    code = CODE.replace("sys.argv[1], sys.argv[2].split(\",\")",
                        f"{cell!r}, {faults!r}")
    res = json.loads(benchkit.run_subprocess(code, chips)
                     .strip().splitlines()[-1])
    assert res == {f: f == "none" for f in faults}


def cells(chips):
    manifest = json.load(open(f"{benchkit.REPO}/BENCHMARK.json"))
    return [w["name"] for w in manifest["workloads"] if w["chips"] == chips]
