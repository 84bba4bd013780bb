"""BigGAN-128's spectral-norm vectors as per-worker field state: carried
through `DQGAN.step` on one and four workers, through a checkpoint, and
through the launcher's resume."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as cfgs
from repro import checkpoint
from repro.configs.base import DQConfig
from repro.core.dqgan import DQGAN
from repro.data import labelled_images
from repro.models import biggan, build
from repro.strategy import Compression, Strategy

SMOKE = cfgs.get("biggan128").reduced()


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _trainer():
    strat = Strategy(compression=Compression(plan="uniform",
                                             compressor="qsgd8_block1024"))
    return DQGAN(field_fn=build(SMOKE).field_fn,
                 dq=DQConfig.from_strategy(strat, optimizer="omd", lr=1e-4,
                                           message="update"))


@pytest.fixture(scope="module")
def one_worker_run():
    """Three jitted steps on one worker from smoke-size weights on a
    labelled batch of 8: the states before and after."""
    params = biggan.init(jax.random.key(4), SMOKE)
    real, label = labelled_images(jax.random.key(5), 8, 128, 3,
                                  SMOKE.n_classes)
    batch = {"real": real, "label": label}
    tr = _trainer()
    state = tr.init(params)
    step = jax.jit(tr.step, static_argnums=(3,))
    states = [state]
    for i in range(3):
        states.append(step(states[-1], batch, jax.random.key(i), True).state)
    return tr, states


def test_field_state_carried_on_one_worker(one_worker_run):
    """The slot starts at the fixed u0 with a worker axis of 1; the first
    step (no optimistic term yet: the field at w0) leaves one power
    iteration at w0 in it; each later step moves it again."""
    tr, states = one_worker_run
    params = states[0].params
    u0 = biggan.init_sn_state(params)
    fs = states[0].field_state
    assert set(fs) == set(u0)
    for k in u0:
        assert fs[k].shape == (1,) + u0[k].shape
        assert np.array_equal(fs[k][0], u0[k])
    _, u1 = biggan.spectral_norm(params, u0, SMOKE.SN_eps)
    for k in u1:
        assert _rel(states[1].field_state[k][0], u1[k]) < 1e-5, k
    for a, b in zip(states[1:], states[2:]):
        moved = [float(jnp.max(jnp.abs(a.field_state[k] - b.field_state[k])))
                 for k in u0]
        assert max(moved) > 0
    assert int(states[-1].step) == 3
    # the stateless field keeps no slot, and so compiles as before
    dc = DQGAN(field_fn=build(cfgs.get("dcgan32")).field_fn, dq=tr.dq)
    dparams = build(cfgs.get("dcgan32")).init(jax.random.key(0))
    assert dc.init(dparams).field_state is None
    assert dc.init_abstract(dparams).field_state is None


@pytest.mark.parametrize("fmt", ["npz", "sharded"])
def test_field_state_checkpoint_round_trip(one_worker_run, tmp_path, fmt):
    tr, states = one_worker_run
    state = states[-1]
    path = str(tmp_path / ("ck.npz" if fmt == "npz" else "ck"))
    if fmt == "npz":
        checkpoint.save(path, state, step=3)
        back = checkpoint.restore(path, tr.init_abstract(state.params))
    else:
        checkpoint.save_sharded(path, state, step=3, n_shards=2)
        back = checkpoint.restore_sharded(path,
                                          tr.init_abstract(state.params))
    names = [n for n, _ in checkpoint._paths(state)]
    assert sum(n.startswith("field_state/") for n in names) == 64
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(back)):
        assert np.array_equal(a, b)


FOUR = r"""
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
import repro.configs as cfgs
from repro.configs.base import DQConfig
from repro.core.dqgan import DQGAN
from repro.data import labelled_images
from repro.models import biggan, build
from repro.strategy import Compression, ExchangePlan, Strategy

mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))


def trainer(field_fn, spmd, comp):
    strat = Strategy(compression=comp,
                     exchange=ExchangePlan(kind="sim", spmd=spmd,
                                           worker_axes=("data",)))
    return DQGAN(field_fn=field_fn, mesh=mesh, batch_spec=P(("data",)),
                 dq=DQConfig.from_strategy(strat, optimizer="omd", lr=1e-2,
                                           message="update"))


def run(tr, params, batch, steps=3):
    with jax.set_mesh(mesh):
        state = tr.init(params)
        step = jax.jit(tr.step, static_argnums=(3,))
        out = [state]
        for i in range(steps):
            out.append(step(out[-1], batch, jax.random.key(i), True).state)
    return out


# a toy stateful field: the state counts its evaluations and keeps the mean
# of the worker's own rows
def toy(params, batch, rng, state):
    x = batch["real"]
    grads = {"w": params["w"] - jnp.mean(x, axis=0)}
    return grads, {"loss": jnp.sum(grads["w"] ** 2)}, {
        "n": state["n"] + 1, "mean": jnp.mean(x, axis=0)}


toy.init_state = lambda params: {"n": jnp.zeros((), jnp.int32),
                                 "mean": jnp.zeros_like(params["w"])}
rows = jnp.arange(8 * 3, dtype=jnp.float32).reshape(8, 3)
out = {}
for spmd, comp in (("shard_map", Compression(plan="uniform",
                                             compressor="qsgd8_block1024")),
                   ("vmap", Compression(compressor="qsgd8_linf"))):
    st = run(trainer(toy, spmd, comp), {"w": jnp.zeros(3)},
             {"real": rows})[-1].field_state
    out["toy_" + spmd] = {"n": np.asarray(st["n"]).tolist(),
                          "mean": np.asarray(st["mean"]).tolist()}

cfg = cfgs.get("biggan128").reduced()
params = biggan.init(jax.random.key(4), cfg)
real, label = labelled_images(jax.random.key(5), 8, 128, 3, cfg.n_classes)
u0 = biggan.init_sn_state(params)
_, u1 = biggan.spectral_norm(params, u0, cfg.SN_eps)
fs = [s.field_state for s in run(
    trainer(build(cfg).field_fn, "shard_map",
            Compression(plan="uniform", compressor="qsgd8_block1024")),
    params, {"real": real, "label": label})]
k = "gen/block0/conv1/w"
last = np.asarray(fs[-1][k])
out["biggan"] = {
    "shape": list(last.shape),
    "sharding": str(fs[-1][k].sharding.spec),
    "step1": max(float(np.max(np.abs(np.asarray(fs[1][j])
                                     - np.asarray(u1[j])[None])))
                 for j in u0),
    "workers_apart": float(np.max(np.abs(last - last[:1]))),
    "finite": bool(all(np.isfinite(np.asarray(v)).all()
                       for v in fs[-1].values()))}
print(json.dumps(out))
"""


def test_field_state_carried_on_four_workers(multidevice):
    """Four workers. A toy stateful field on the shard_map and vmap paths:
    each worker's row holds its own evaluation count and the mean of its
    own rows. BigGAN on shard_map: step 1 evaluates every worker's field
    at w0 (no optimistic term yet), so every row holds the same power
    iteration there; from step 2 each worker's lookahead point carries its
    own previous message, and the rows part. (The vmap path cannot run
    BigGAN over a mesh: XLA's partitioner splits the grouped convolution
    that vmap makes of per-worker kernels into groups that no longer
    divide its output features.)"""
    res = json.loads(multidevice(FOUR, n_devices=4).strip().splitlines()[-1])
    own = [[3 * (2 * m) + c + 1.5 for c in range(3)] for m in range(4)]
    for spmd in ("shard_map", "vmap"):
        assert res["toy_" + spmd] == {"n": [3, 3, 3, 3], "mean": own}, res
    r = res["biggan"]
    assert r["shape"] == [4, 8 * 16] and "data" in r["sharding"], r
    assert r["step1"] < 1e-5 and r["finite"], r
    assert r["workers_apart"] > 0, r


def test_launcher_trains_and_resumes(tmp_path, monkeypatch):
    """`launch.train --arch biggan128 --smoke` trains on labelled batches,
    saves the field state with the rest of DQState, and a resume restores
    it and goes on."""
    from repro.launch import train

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    ck = str(tmp_path / "ck.npz")
    flags = ["--arch", "biggan128", "--smoke", "--batch", "4",
             "--optimizer", "omd", "--lr", "1e-4", "--log-every", "1",
             "--comm-plan", "uniform", "--compressor", "qsgd8_block1024",
             "--checkpoint", ck]
    first = train.run(flags + ["--steps", "2"])
    assert all(np.isfinite(r["loss"]) for r in first.history)
    saved = first.state.field_state
    second = train.run(flags + ["--steps", "3", "--resume", ck])
    assert [r["step"] for r in second.history] == [2]
    assert int(second.state.step) == 3
    moved = max(float(jnp.max(jnp.abs(second.state.field_state[k]
                                      - saved[k]))) for k in saved)
    assert moved > 0
