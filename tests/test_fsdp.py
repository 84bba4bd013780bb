"""Compressed-gradient FSDP (DESIGN.md §15): strategy-lattice validation,
shard-aware bucket layouts, the fsdp == replicated-DDP equivalence on 1
and 8 devices (GAN and transformer configs), single-trace compiled
steps, the reduce-scatter/all-gather HLO structure check, and the
skipped-leaf ledger accounting the train-log warning surfaces."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.comm import buckets as B
from repro.comm.ledger import CommLedger
from repro.comm.planner import plan_comm
from repro.configs.base import DQConfig
from repro.core.dqgan import DQGAN
from repro.strategy import (
    Compression,
    ExchangePlan,
    MomentCompression,
    Participation,
    Strategy,
    StrategyError,
    get_preset,
)

KEY = jax.random.key(0)


# --------------------------------------------------------------------------- #
# strategy lattice: presets + every invalid fsdp combination is a
# StrategyError naming the offending field
# --------------------------------------------------------------------------- #
def test_fsdp_presets():
    z2, z3 = get_preset("fsdp_zero2"), get_preset("fsdp_zero3")
    assert z2.exchange.fsdp and z2.exchange.zero_stage == 2
    assert z3.exchange.fsdp and z3.exchange.zero_stage == 3
    assert z3.moments.compressor == "qsgd8_linf"
    assert Strategy.from_json(z3.to_json()) == z3


@pytest.mark.parametrize("make,field", [
    # satellite: partial participation composes with replicated exchange
    # only — masked reduce-scatter would mis-average every shard
    (lambda: Strategy(
        compression=Compression(plan="uniform"),
        exchange=ExchangePlan(kind="two_phase", parallelism="fsdp"),
        participation=Participation(fraction=0.5)),
     "participation.fraction"),
    (lambda: ExchangePlan(kind="sim", parallelism="fsdp"), "exchange.kind"),
    (lambda: ExchangePlan(kind="sim", spmd="vmap", parallelism="fsdp"),
     "exchange.parallelism"),
    (lambda: ExchangePlan(kind="two_phase", parallelism="fsdp",
                          zero_stage=1), "exchange.zero_stage"),
    (lambda: ExchangePlan(kind="two_phase", parallelism="fsdp",
                          fsdp_axis="model", worker_axes=("data",)),
     "exchange.fsdp_axis"),
    # fsdp shards flat buckets; the bucketing pipeline is mandatory
    (lambda: Strategy(
        exchange=ExchangePlan(kind="two_phase", parallelism="fsdp")),
     "compression.plan"),
    # a moments component without fsdp would be silently ignored
    (lambda: Strategy(moments=MomentCompression(compressor="qsgd8_linf")),
     "moments.compressor"),
])
def test_invalid_fsdp_combinations_raise(make, field):
    with pytest.raises(StrategyError, match=field.replace(".", r"\.")):
        make()


# --------------------------------------------------------------------------- #
# shard-aware bucket layouts (comm.buckets, DESIGN.md §15.1)
# --------------------------------------------------------------------------- #
def test_layout_buckets_data_sharded_leaf_at_local_shape():
    shapes = {"w": (16, 4), "b": (4,)}
    specs = {"w": P("data"), "b": P()}
    lay = B.build_layout(shapes, specs, n_workers=4,
                         shard_axes=("data",), axis_sizes={"data": 4})
    assert not lay.skipped
    slots = {s.path: s for b in lay.buckets for s in b.slots}
    w = next(s for p, s in slots.items() if "w" in p)
    assert w.local and w.shape == (4, 4)        # 16/4 rows per owner
    b_ = next(s for p, s in slots.items() if "b" in p)
    assert not b_.local and b_.shape == (4,)


def test_layout_skips_leaf_sharded_outside_shard_axes():
    shapes = {"w": (16, 4)}
    lay = B.build_layout(shapes, {"w": P("model")}, n_workers=4,
                         shard_axes=("data",),
                         axis_sizes={"data": 4, "model": 2})
    assert len(lay.skipped) == 1 and not lay.buckets


def test_layout_treats_size1_axis_sharding_as_replication():
    # a degenerate model_n=1 mesh leaves P("model") specs on leaves;
    # "sharding" over a size-1 axis is replication and must not skip
    shapes = {"w": (16, 4)}
    lay = B.build_layout(shapes, {"w": P("model")}, n_workers=4,
                         axis_sizes={"data": 4, "model": 1})
    assert not lay.skipped and lay.buckets
    # without axis_sizes the spec is (conservatively) a real shard
    lay2 = B.build_layout(shapes, {"w": P("model")}, n_workers=4)
    assert len(lay2.skipped) == 1


# --------------------------------------------------------------------------- #
# skipped-leaf accounting (the train-log warning's data source)
# --------------------------------------------------------------------------- #
def test_ledger_skipped_leaf_summary():
    shapes = {"w": (16, 4), "t": (8, 8)}
    specs = {"w": P("model"), "t": P()}
    lay = B.build_layout(shapes, specs, n_workers=4)
    plan = plan_comm(lay, "qsgd8_linf", "uniform")
    led = CommLedger.from_plan(lay, plan, "two_phase", 4, "qsgd8_linf")
    n, byts = led.skipped_leaves()
    assert n == 1 and byts > 0
    s = led.summary()
    assert s["skipped_leaves"] == 1
    assert s["skipped_leaf_bytes_per_step"] == round(byts)
    # nothing skipped -> the keys stay absent (no noise in clean runs)
    lay2 = B.build_layout({"t": (8, 8)}, {"t": P()}, n_workers=4)
    led2 = CommLedger.from_plan(lay2, plan_comm(lay2, "qsgd8_linf", "uniform"),
                                "two_phase", 4, "qsgd8_linf")
    assert led2.skipped_leaves() == (0, 0)
    assert "skipped_leaves" not in led2.summary()


# --------------------------------------------------------------------------- #
# single-device (W=1) fsdp == replicated DDP, GAN + quadratic configs
# --------------------------------------------------------------------------- #
_A = jnp.array(np.random.RandomState(0).randn(8, 8), jnp.float32)


def _bilinear_field(params, batch, rng):
    x, y = params["x"], params["y"]
    s = 1.0 + jnp.mean(batch)
    return ({"x": s * (_A @ y), "y": -s * (_A.T @ x)},
            {"loss": x @ _A @ y})


def _replicated(kind="exact"):
    return Strategy(
        compression=Compression(compressor="identity", error_feedback=False,
                                plan="uniform"),
        exchange=ExchangePlan(kind=kind))


def _fsdp(zero_stage, kind="exact"):
    return Strategy(
        compression=Compression(compressor="identity", error_feedback=False,
                                plan="uniform"),
        exchange=ExchangePlan(kind=kind, parallelism="fsdp",
                              zero_stage=zero_stage),
        moments=MomentCompression(compressor="identity",
                                  error_feedback=False))


def _train(st, field, params, batch, opt, steps=5):
    dq = DQConfig.from_strategy(st, optimizer=opt, lr=0.05)
    tr = DQGAN(field_fn=field, dq=dq)
    sched = tr.strategy.schedule.runtime()
    state = tr.init(params)
    step = jax.jit(tr.step, static_argnums=(3,))
    for i in range(steps):
        state = step(state, batch, KEY, sched.is_exchange_step(i)).state
    return jax.device_get(state.params)


@pytest.mark.parametrize("zero_stage", [2, 3])
@pytest.mark.parametrize("opt", ["adam", "oadam", "sgd"])
def test_fsdp_matches_replicated_1dev(zero_stage, opt):
    params = {"x": jnp.ones(8), "y": jnp.ones(8)}
    batch = jnp.arange(8, dtype=jnp.float32).reshape(8, 1) / 8.0
    a = _train(_replicated(), _bilinear_field, params, batch, opt)
    b = _train(_fsdp(zero_stage), _bilinear_field, params, batch, opt)
    for k in "xy":
        np.testing.assert_allclose(a[k], b[k], rtol=1e-5, atol=1e-6)


def test_fsdp_matches_replicated_1dev_gan():
    from repro.models import gan
    cfg = gan.GANConfig(image_size=0, data_dim=2, hidden=16, latent_dim=8)
    params = gan.init(KEY, cfg)
    field = gan.gan_field_fn(cfg)
    batch = {"real": jax.random.normal(KEY, (16, 2))}
    a = _train(_replicated(), field, params, batch, "oadam", steps=4)
    b = _train(_fsdp(3), field, params, batch, "oadam", steps=4)
    for ka, kb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(ka, kb, rtol=1e-5, atol=1e-6)


def test_fsdp_state_slots():
    params = {"x": jnp.ones(8), "y": jnp.ones(8)}
    dq = DQConfig.from_strategy(_fsdp(3), optimizer="adam", lr=0.05)
    tr = DQGAN(field_fn=_bilinear_field, dq=dq)
    st = tr.init(params)
    assert st.m is None and st.v is None          # moments live sharded
    assert set(st.fsdp) == {"0"}                  # one flat bucket
    slot = st.fsdp["0"]
    assert set(slot) == {"m", "v", "w", "age"}    # zero3 carries params
    dq2 = DQConfig.from_strategy(_fsdp(2), optimizer="adam", lr=0.05)
    st2 = DQGAN(field_fn=_bilinear_field, dq=dq2).init(params)
    assert set(st2.fsdp["0"]) == {"m", "v", "age"}


# --------------------------------------------------------------------------- #
# 8-device: equivalence, trace count, HLO structure, skipped-leaf error
# --------------------------------------------------------------------------- #
FSDP_EQUIV_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.configs.base import DQConfig
from repro.core.dqgan import DQGAN
from repro.obs.hlo import assert_fsdp_structure
from repro.strategy import (Strategy, Compression, ExchangePlan,
                            MomentCompression)

A = jnp.array(np.random.RandomState(0).randn(64, 64), jnp.float32)
def field(params, batch, rng):
    x, y = params["x"], params["y"]
    s = 1.0 + jnp.mean(batch)
    return {"x": s * (A @ y), "y": -s * (A.T @ x)}, {"loss": x @ A @ y}

mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
params = {"x": jnp.ones(64), "y": jnp.ones(64)}
batch = jnp.arange(16, dtype=jnp.float32).reshape(16, 1) / 16.0
traces = [0]

def counting_field(params, batch, rng):
    traces[0] += 1
    return field(params, batch, rng)

def run(st, steps=6, opt="adam", f=field, hlo=False):
    dq = DQConfig.from_strategy(st, optimizer=opt, lr=0.05)
    tr = DQGAN(field_fn=f, dq=dq, mesh=mesh,
               param_specs={"x": P(), "y": P()}, batch_spec=P(("data",)))
    sched = tr.strategy.schedule.runtime()
    with jax.set_mesh(mesh):
        state = tr.init(params)
        step = jax.jit(tr.step, static_argnums=(3,))
        txt = (step.lower(state, batch, jax.random.key(7), True)
               .compile().as_text() if hlo else None)
        for i in range(steps):
            state = step(state, batch, jax.random.key(7),
                         sched.is_exchange_step(i)).state
    return jax.device_get(state.params), txt

repl = Strategy(
    compression=Compression(compressor="identity", error_feedback=False,
                            plan="uniform"),
    exchange=ExchangePlan(kind="exact", worker_axes=("data",)))
for zs in (2, 3):
    fsdp = Strategy(
        compression=Compression(compressor="identity", error_feedback=False,
                                plan="uniform"),
        exchange=ExchangePlan(kind="exact", parallelism="fsdp", zero_stage=zs,
                              worker_axes=("data",)),
        moments=MomentCompression(compressor="identity",
                                  error_feedback=False))
    for opt in ("adam", "sgd"):
        a, _ = run(repl, opt=opt)
        b, _ = run(fsdp, opt=opt)
        for k in "xy":
            np.testing.assert_allclose(a[k], b[k], rtol=1e-5, atol=1e-6)
print("EQUIV-OK")

# compressed zero3: one trace across all rounds + the ZeRO wire shape
fsdp_q = Strategy(
    compression=Compression(plan="uniform"),
    exchange=ExchangePlan(kind="two_phase", parallelism="fsdp", zero_stage=3,
                          worker_axes=("data",)),
    moments=MomentCompression(compressor="qsgd8_linf"))
traces[0] = 0
p, txt = run(fsdp_q, steps=6, f=counting_field, hlo=True)
assert all(np.isfinite(v).all() for v in p.values())
assert traces[0] == 1, f"compressed fsdp retraced: {traces[0]} traces"
print("TRACE-OK")
assert_fsdp_structure(txt, compressed=True)
print("HLO-OK")

# a leaf sharded over a real (size>1) non-worker axis cannot enter a
# flat bucket -> init fails fast naming the leaf
mesh2 = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
dq = DQConfig.from_strategy(fsdp_q, optimizer="adam", lr=0.05)
tr = DQGAN(field_fn=field, dq=dq, mesh=mesh2,
           param_specs={"x": P("model"), "y": P()}, batch_spec=P(("data",)))
with jax.set_mesh(mesh2):
    try:
        tr.init(params)
    except ValueError as e:
        assert "skipped leaf" in str(e), e
        print("SKIP-ERR-OK")
print("ALL-OK")
"""


@pytest.mark.multidevice
def test_fsdp_equivalence_8dev(multidevice):
    out = multidevice(FSDP_EQUIV_SCRIPT)
    for tag in ("EQUIV-OK", "TRACE-OK", "HLO-OK", "SKIP-ERR-OK", "ALL-OK"):
        assert tag in out, out


FSDP_GAN_8DEV_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.configs.base import DQConfig
from repro.core.dqgan import DQGAN
from repro.models import gan
from repro.strategy import (Strategy, Compression, ExchangePlan,
                            MomentCompression)

key = jax.random.key(0)
cfg = gan.GANConfig(image_size=0, data_dim=2, hidden=32, latent_dim=8)
params = gan.init(key, cfg)
field = gan.gan_field_fn(cfg)
mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
batch = {"real": jax.random.normal(key, (16, 2))}
pspecs = jax.tree.map(lambda x: P(), params)

def run(st, steps=4):
    dq = DQConfig.from_strategy(st, optimizer="oadam", lr=0.02)
    tr = DQGAN(field_fn=field, dq=dq, mesh=mesh, param_specs=pspecs,
               batch_spec=P(("data",)))
    sched = tr.strategy.schedule.runtime()
    with jax.set_mesh(mesh):
        state = tr.init(params)
        step = jax.jit(tr.step, static_argnums=(3,))
        for i in range(steps):
            state = step(state, batch, key, sched.is_exchange_step(i)).state
    return jax.device_get(state.params)

repl = Strategy(
    compression=Compression(compressor="identity", error_feedback=False,
                            plan="uniform"),
    exchange=ExchangePlan(kind="exact", worker_axes=("data",)))
a = run(repl)
for zs in (2, 3):
    fsdp = Strategy(
        compression=Compression(compressor="identity", error_feedback=False,
                                plan="uniform"),
        exchange=ExchangePlan(kind="exact", parallelism="fsdp", zero_stage=zs,
                              worker_axes=("data",)),
        moments=MomentCompression(compressor="identity",
                                  error_feedback=False))
    b = run(fsdp)
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(la, lb, rtol=1e-5, atol=1e-6)
print("OK")
"""


@pytest.mark.multidevice
def test_fsdp_gan_equivalence_8dev(multidevice):
    out = multidevice(FSDP_GAN_8DEV_SCRIPT)
    assert "OK" in out


FSDP_LM_8DEV_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
import repro.configs as cfgs
from repro.configs.base import DQConfig
from repro.core.dqgan import DQGAN
from repro.data import synthetic_lm_batch
from repro.models import build
from repro.strategy import (Strategy, Compression, ExchangePlan,
                            MomentCompression)

key = jax.random.key(0)
cfg = cfgs.get("gemma-2b").reduced()
bundle = build(cfg)
params = bundle.init(key, max_seq=64)
pspecs = jax.tree.map(lambda x: P(), params)
mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
batch = synthetic_lm_batch(key, 8, 32, cfg.vocab_size)

def run(st, steps=3):
    dq = DQConfig.from_strategy(st, optimizer="adam", lr=1e-3)
    tr = DQGAN(field_fn=bundle.field_fn, dq=dq, mesh=mesh, param_specs=pspecs,
               batch_spec=P(("data",)))
    sched = tr.strategy.schedule.runtime()
    with jax.set_mesh(mesh):
        state = tr.init(params)
        step = jax.jit(tr.step, static_argnums=(3,))
        for i in range(steps):
            state = step(state, batch, key, sched.is_exchange_step(i)).state
    return jax.device_get(state.params)

repl = Strategy(
    compression=Compression(compressor="identity", error_feedback=False,
                            plan="uniform"),
    exchange=ExchangePlan(kind="exact", worker_axes=("data",)))
fsdp = Strategy(
    compression=Compression(compressor="identity", error_feedback=False,
                            plan="uniform"),
    exchange=ExchangePlan(kind="exact", parallelism="fsdp", zero_stage=3,
                          worker_axes=("data",)),
    moments=MomentCompression(compressor="identity", error_feedback=False))
a, b = run(repl), run(fsdp)
for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
    np.testing.assert_allclose(la, lb, rtol=1e-5, atol=1e-6)
print("OK")
"""


@pytest.mark.multidevice
def test_fsdp_transformer_equivalence_8dev(multidevice):
    out = multidevice(FSDP_LM_8DEV_SCRIPT)
    assert "OK" in out
