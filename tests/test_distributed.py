"""Multi-device tests (8 forced host devices, run in subprocesses because
jax pins the device count at first init — see conftest.run_multidevice)."""
import pytest

pytestmark = pytest.mark.multidevice


DELTA1_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.configs.base import DQConfig
from repro.core.dqgan import DQGAN

A = jnp.array(np.random.RandomState(0).randn(4,4), jnp.float32)
def field(params, batch, rng):
    x, y = params["x"], params["y"]
    return {"x": A @ y, "y": -(A.T @ x)}, {"loss": x @ A @ y}

mesh = jax.make_mesh((2,2,2), ("pod","data","model"), axis_types=(AxisType.Auto,) * 3)
params = {"x": jnp.ones(4), "y": jnp.ones(4)}
pspecs = {"x": P(), "y": P()}
batch = jnp.zeros((8,1))

def run(exchange, compressor):
    dq = DQConfig(optimizer="omd", compressor=compressor, exchange=exchange,
                  lr=0.05, worker_axes=("pod","data"))
    tr = DQGAN(field_fn=field, dq=dq, mesh=mesh, param_specs=pspecs,
               batch_spec=P(("pod","data")))
    with jax.set_mesh(mesh):
        st = tr.init(params)
        step = jax.jit(tr.step)
        for i in range(25):
            st = step(st, batch, jax.random.key(7)).state
        return jax.device_get(st.params)

exact = run("exact", "identity")
sim_id = run("sim", "identity")
np.testing.assert_array_equal(exact["x"], sim_id["x"])   # delta=1 bit-exact
np.testing.assert_array_equal(exact["y"], sim_id["y"])

# quantized strategies all converge toward the saddle and stay close to exact
for exch in ("sim", "allgather", "two_phase"):
    q = run(exch, "qsgd8_linf")
    d = float(np.linalg.norm(q["x"] - exact["x"]) + np.linalg.norm(q["y"] - exact["y"]))
    assert d < 0.5, (exch, d)
print("OK")
"""


def test_delta1_equivalence_and_strategies(multidevice):
    out = multidevice(DELTA1_SCRIPT)
    assert "OK" in out


EXCHANGE_SEMANTICS_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.core import compressors as C
from repro.core import exchange as X

mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
W = 8
comp = C.get("qsgd8_linf")
shape = (16, 32)
key = jax.random.key(0)
ps = jax.random.normal(key, (W,) + shape)  # per-worker messages

# reference: mean over workers of each worker's dequantized message with the
# SAME per-worker fold_in(key_leaf, widx) keys the exchange uses internally.
def ref_mean(strategy):
    outs = []
    for w in range(W):
        k = jax.random.fold_in(jax.random.fold_in(key, w), 0)
        outs.append(comp.roundtrip(ps[w], k))
    return jnp.mean(jnp.stack(outs), 0)

def worker(p, key):
    widx = jax.lax.axis_index(("data",))
    kw = jax.random.fold_in(jax.random.fold_in(key, widx), 0)
    plan = X.plan_leaf("allgather", shape, P(), W)
    q, _ = X.exchange_leaf(comp, plan, p[0], {"e1": jnp.zeros(shape)}, kw,
                           ("data",), W, True)
    return q[None]

f = jax.shard_map(worker, mesh=mesh, in_specs=(P("data"), P()),
                  out_specs=P("data"), axis_names={"data"}, check_vma=False)
with jax.set_mesh(mesh):
    q = f(ps, key)
np.testing.assert_allclose(np.asarray(q[0]), np.asarray(ref_mean("allgather")),
                           rtol=1e-5, atol=1e-5)
for w in range(1, W):  # every worker received the same q-hat
    np.testing.assert_allclose(np.asarray(q[w]), np.asarray(q[0]), atol=1e-6)

# two_phase: phase-2 requantization error must be bounded by the quantizer's
# per-chunk resolution; and with the identity compressor it's exact psum-mean.
plan2 = X.plan_leaf("two_phase", shape, P(), W)
assert plan2["strategy"] == "two_phase" and plan2["chunk_axis"] == 1

def worker2(p, key):
    widx = jax.lax.axis_index(("data",))
    kw = jax.random.fold_in(key, widx)
    st = X.ef_state_zeros(plan2, shape, jnp.float32, W, True)
    q, _ = X.exchange_leaf(C.get("identity"), plan2, p[0], st, kw,
                           ("data",), W, True)
    return q[None]

f2 = jax.shard_map(worker2, mesh=mesh, in_specs=(P("data"), P()),
                   out_specs=P("data"), axis_names={"data"}, check_vma=False)
with jax.set_mesh(mesh):
    q2 = f2(ps, key)
np.testing.assert_allclose(np.asarray(q2[0]), np.asarray(jnp.mean(ps, 0)),
                           rtol=1e-5, atol=1e-6)
print("OK")
"""


def test_exchange_semantics(multidevice):
    out = multidevice(EXCHANGE_SEMANTICS_SCRIPT)
    assert "OK" in out


SHARDED_TRAIN_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
import repro.configs as cfgs
from repro.configs.base import DQConfig
from repro.core.dqgan import DQGAN
from repro.models import build
from repro.parallel import sharding as shd
from repro.data import synthetic_lm_batch

# real (reduced) model trained data-parallel x tensor-parallel on 8 devices
mesh = jax.make_mesh((2,2,2), ("pod","data","model"), axis_types=(AxisType.Auto,) * 3)
cfg = cfgs.get("gemma-2b").reduced()
bundle = build(cfg)
key = jax.random.key(0)
params = bundle.init(key, max_seq=64)
pspecs = shd.param_specs(params, cfg, "dp", mesh)
params = jax.tree.map(lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
                      params, pspecs, is_leaf=lambda x: hasattr(x, "shape"))
dq = DQConfig(optimizer="oadam", compressor="qsgd8_linf", exchange="two_phase",
              message="grad", lr=3e-3, worker_axes=("pod","data"))
tr = DQGAN(field_fn=bundle.field_fn, dq=dq, mesh=mesh, param_specs=pspecs,
           batch_spec=P(("pod","data")))
with jax.set_mesh(mesh):
    st = tr.init(params)
    step = jax.jit(tr.step, donate_argnums=0)
    losses = []
    for i in range(20):
        batch = synthetic_lm_batch(jax.random.fold_in(key, i), 8, 32,
                                   cfg.vocab_size)
        out = step(st, batch, key)
        st = out.state
        losses.append(float(out.metrics["loss"]))
assert all(np.isfinite(losses)), losses
assert losses[-1] < losses[0] - 0.3, losses  # actually learning
print("OK", losses[0], losses[-1])
"""


def test_sharded_model_training(multidevice):
    out = multidevice(SHARDED_TRAIN_SCRIPT)
    assert "OK" in out


FSDP_LOWER_SCRIPT = r"""
import jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
import repro.configs as cfgs
from repro.configs.base import DQConfig, InputShape
from repro.core.dqgan import DQGAN
from repro.launch import specs as S
from repro.models import build
from jax.sharding import NamedSharding

# mode B: FSDP over 'data' + TP over 'model', DQGAN workers = pods.
mesh = jax.make_mesh((2,2,2), ("pod","data","model"), axis_types=(AxisType.Auto,) * 3)
cfg = cfgs.get("qwen3-moe-30b-a3b").reduced()
bundle = build(cfg)
with jax.set_mesh(mesh):
    params_sds, pspecs = S.abstract_params(cfg, mesh, "fsdp", 8)
    # shard_map manual-over-pod + FSDP auto axes trips an XLA partitioner
    # CHECK (DESIGN.md §2) -> the vmap worker formulation is used instead.
    dq = DQConfig(optimizer="omd", compressor="qsgd8_linf",
                  exchange="sim", spmd="vmap", worker_axes=("pod",))
    tr = DQGAN(field_fn=bundle.field_fn, dq=dq, mesh=mesh, param_specs=pspecs,
               batch_spec=P(("pod",)))
    st = tr.init_abstract(params_sds)
    shape = InputShape("t", 32, 8, "train")
    batch = S.train_batch_specs(cfg, shape, mesh)
    compiled = jax.jit(tr.step).lower(st, batch, S.key_spec()).compile()
    txt = compiled.as_text()
    assert "all-reduce" in txt or "all-gather" in txt
    print("OK")
"""


def test_fsdp_moe_lowering(multidevice):
    out = multidevice(FSDP_LOWER_SCRIPT)
    assert "OK" in out


INIT_PLACEMENT_SCRIPT = r"""
import jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
from repro.configs.base import DQConfig
from repro.core.dqgan import DQGAN
from repro.models.gan import GANConfig, gan_field_fn, mlp_gan_init
from repro.strategy import Compression, ExchangePlan, Strategy

mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
cfg = GANConfig(name="mix", image_size=0, data_dim=2, latent_dim=16,
                hidden=128)
params = mlp_gan_init(jax.random.key(0), cfg)
strat = Strategy(compression=Compression(plan="uniform", bucket_mb=0.03),
                 exchange=ExchangePlan(kind="two_phase",
                                       worker_axes=("data",)))
tr = DQGAN(field_fn=gan_field_fn(cfg),
           dq=DQConfig.from_strategy(strat, optimizer="omd", lr=1e-2),
           mesh=mesh, batch_spec=P(("data",)))
traces = []

def counting(st, batch, key, do_exchange):
    traces.append(1)
    return tr.step(st, batch, key, do_exchange)

batch = {"real": jax.random.normal(jax.random.key(1), (64, 2))}
with jax.set_mesh(mesh):
    st0 = tr.init(params)
    step = jax.jit(counting, static_argnums=(3,))
    st1 = step(st0, batch, jax.random.key(7), True).state
    st2 = step(st1, batch, jax.random.key(7), True).state
assert len(traces) == 1, f"{len(traces)} traces"
shard0 = [x.sharding for x in jax.tree.leaves(st0)]
assert shard0 == [x.sharding for x in jax.tree.leaves(st1)]
# per-worker slots: one worker's shard on each device
for x in jax.tree.leaves((st0.prev_grad, st0.ef)):
    assert len({s.device for s in x.addressable_shards}) == 8
    assert all(s.data.shape[0] == 1 for s in x.addressable_shards)
print("OK")
"""


def test_init_placement_one_trace(multidevice):
    """`DQGAN.init` places every leaf as the step returns it: two steps
    trace once, and init's shardings equal the step output's."""
    out = multidevice(INIT_PLACEMENT_SCRIPT)
    assert "OK" in out
