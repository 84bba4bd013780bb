"""repro.strategy: presets, exact JSON round-trip, construction-time
validation (StrategyError naming the offending field), the DQConfig
legacy shim (bit-exact vs the flat flag-bag spelling on 1 and 8
devices), lr_mults group validation, and the checkpoint resume guard."""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint
from repro.configs.base import DQConfig
from repro.core.dqgan import DQGAN
from repro.strategy import (
    PRESETS,
    Compression,
    ExchangePlan,
    Participation,
    Schedule,
    Strategy,
    StrategyError,
    get_preset,
)

KEY = jax.random.key(0)


# --------------------------------------------------------------------------- #
# presets + JSON round-trip
# --------------------------------------------------------------------------- #
def test_every_preset_constructs_and_roundtrips_exactly():
    assert len(PRESETS) >= 5
    for name, st in PRESETS.items():
        s = st.to_json()
        back = Strategy.from_json(s)
        assert back == st, name
        # canonical: serialize(deserialize(s)) is byte-identical
        assert back.to_json() == s, name
        assert back.short_hash() == st.short_hash(), name


def test_hash_is_structural():
    a = get_preset("paper_dqgan")
    b = a.evolve(staleness_tau=1)  # no-op evolve
    assert a.short_hash() == b.short_hash()
    c = a.evolve(schedule="delayed", staleness_tau=2)
    assert c.short_hash() != a.short_hash()
    assert "schedule.kind: 'every_step' != 'delayed'" in a.diff(c)


def test_unknown_preset_and_json_fields_raise():
    with pytest.raises(StrategyError, match="preset"):
        get_preset("nope")
    with pytest.raises(StrategyError, match="unknown component"):
        Strategy.from_json('{"compresion": {}}')
    with pytest.raises(StrategyError, match="unknown field"):
        Strategy.from_json('{"schedule": {"K": 4}}')
    with pytest.raises(StrategyError, match="invalid JSON"):
        Strategy.from_json("{not json")


# --------------------------------------------------------------------------- #
# every documented invalid combination is a StrategyError naming the field
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("make,field", [
    # schedule lattice
    (lambda: Schedule(kind="every_step", tau=2), "schedule.tau"),
    (lambda: Schedule(kind="delayed", k=3), "schedule.k"),
    (lambda: Schedule(kind="local_k", k=0), "schedule.k"),
    (lambda: Schedule.delayed(0), "schedule.tau"),
    (lambda: Schedule(kind="bogus"), "schedule.kind"),
    # compression
    (lambda: Compression(compressor="bogus"), "compression.compressor"),
    (lambda: Compression(plan="bogus"), "compression.plan"),
    (lambda: Compression(plan="delta_budget"), "compression.budget_mb"),
    (lambda: Compression(plan="uniform", budget_mb=1.0),
     "compression.budget_mb"),
    (lambda: Compression(bucket_mb=0.0), "compression.bucket_mb"),
    (lambda: Compression(ef_dtype="int8"), "compression.ef_dtype"),
    # exchange
    (lambda: ExchangePlan(kind="bogus"), "exchange.kind"),
    (lambda: ExchangePlan(spmd="bogus"), "exchange.spmd"),
    # participation
    (lambda: Participation(fraction=0.0), "participation.fraction"),
    (lambda: Participation(fraction=1.5), "participation.fraction"),
    (lambda: Participation(straggler_profile="bogus"),
     "participation.straggler_profile"),
    # cross-field
    (lambda: Strategy(participation=Participation(fraction=0.5),
                      exchange=ExchangePlan(kind="exact")),
     "participation.fraction"),
    (lambda: Strategy(compression=Compression(plan="uniform"),
                      exchange=ExchangePlan(spmd="vmap")),
     "compression.plan"),
    (lambda: Strategy(exchange=ExchangePlan(kind="two_phase", spmd="vmap")),
     "exchange.kind"),
])
def test_invalid_combinations_raise_with_field_name(make, field):
    with pytest.raises(StrategyError) as ei:
        make()
    assert field in str(ei.value), str(ei.value)


def test_strategy_error_is_a_value_error():
    assert issubclass(StrategyError, ValueError)


# --------------------------------------------------------------------------- #
# the legacy DQConfig shim
# --------------------------------------------------------------------------- #
def test_legacy_flag_bag_builds_equal_strategy():
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        dq = DQConfig(optimizer="omd", compressor="qsgd8_linf",
                      exchange="sim", schedule="delayed", staleness_tau=2,
                      worker_axes=())
    assert any(issubclass(x.category, DeprecationWarning) for x in w)
    want = Strategy(exchange=ExchangePlan(kind="sim", worker_axes=()),
                    schedule=Schedule.delayed(2))
    assert dq.strategy == want
    # the blessed spelling mirrors back into the flat fields, no warning
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        dq2 = DQConfig.from_strategy(want, optimizer="omd")
    assert not [x for x in w if issubclass(x.category, DeprecationWarning)]
    assert dq2.schedule == "delayed" and dq2.staleness_tau == 2
    assert dq2.strategy == want and dq2 == dq


def test_legacy_bad_combos_raise_at_construction():
    with pytest.raises(StrategyError, match="schedule.tau"):
        DQConfig(staleness_tau=2)
    with pytest.raises(StrategyError, match="compression.budget_mb"):
        DQConfig(comm_plan="delta_budget")
    with pytest.raises(StrategyError, match="participation.fraction"):
        DQConfig(participation=0.5, exchange="exact")


def test_from_strategy_rejects_distribution_keywords():
    with pytest.raises(ValueError, match="strategy fields"):
        DQConfig.from_strategy(Strategy(), compressor="sign")


def test_replace_on_blessed_config_does_not_warn():
    """dataclasses.replace(dq, lr=...) is the documented optimizer-side
    patch path (gan_common dq_overrides) — it must not trip the legacy
    deprecation warning just because the carried strategy is non-default."""
    dq = DQConfig.from_strategy(
        Strategy(exchange=ExchangePlan(worker_axes=())), optimizer="omd")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        dq2 = dataclasses.replace(dq, lr=1e-4)
    assert not [x for x in w if issubclass(x.category, DeprecationWarning)]
    assert dq2.strategy == dq.strategy and dq2.lr == 1e-4


def test_dqgan_takes_a_strategy_directly():
    st = Strategy(exchange=ExchangePlan(worker_axes=()))
    tr = DQGAN(field_fn=lambda p, b, k: (p, {}), strategy=st)
    assert tr.strategy == st and tr.dq.strategy == st
    with pytest.raises(ValueError, match="disagree"):
        DQGAN(field_fn=lambda p, b, k: (p, {}),
              dq=DQConfig.from_strategy(st),
              strategy=st.evolve(compressor="sign"))


# --------------------------------------------------------------------------- #
# legacy spelling → Strategy → training is bit-exact (1 device; the
# 8-device variants run under the forced-host-device subprocess)
# --------------------------------------------------------------------------- #
A = jnp.asarray(np.random.RandomState(3).randn(6, 6), jnp.float32)


def _field(params, batch, rng):
    x, y = params["x"], params["y"]
    return {"x": A @ y, "y": -(A.T @ x)}, {"loss": x @ A @ y}


def _train(dq, steps=8):
    tr = DQGAN(field_fn=_field, dq=dq)
    st = tr.init({"x": jnp.ones(6), "y": jnp.ones(6)})
    step = jax.jit(tr.step, static_argnums=(3,))
    sched = tr.strategy.schedule.runtime()
    for i in range(steps):
        st = step(st, None, KEY, sched.is_exchange_step(i)).state
    return jax.device_get(st.params)


@pytest.mark.parametrize("legacy", [
    dict(schedule="every_step"),
    dict(schedule="local_k", local_k=4),
    dict(schedule="delayed", staleness_tau=2),
])
def test_legacy_vs_strategy_training_bit_exact(legacy):
    dq_legacy = DQConfig(optimizer="omd", compressor="qsgd8_linf",
                         exchange="sim", lr=0.05, worker_axes=(), **legacy)
    st = Strategy.from_legacy(exchange="sim", worker_axes=(), **legacy)
    dq_typed = DQConfig.from_strategy(st, optimizer="omd", lr=0.05)
    assert dq_legacy == dq_typed
    a, b = _train(dq_legacy), _train(dq_typed)
    for k in ("x", "y"):
        np.testing.assert_array_equal(a[k], b[k])


STRATEGY_8DEV_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.configs.base import DQConfig
from repro.core.dqgan import DQGAN
from repro.strategy import Strategy

A = jnp.array(np.random.RandomState(0).randn(4, 4), jnp.float32)
def field(params, batch, rng):
    x, y = params["x"], params["y"]
    s = 1.0 + jnp.mean(batch)
    return {"x": s * (A @ y), "y": -s * (A.T @ x)}, {"loss": x @ A @ y}

mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
params = {"x": jnp.ones(4), "y": jnp.ones(4)}
batch = jnp.arange(8, dtype=jnp.float32).reshape(8, 1) / 8.0

def run(dq, steps=12):
    tr = DQGAN(field_fn=field, dq=dq, mesh=mesh,
               param_specs={"x": P(), "y": P()}, batch_spec=P(("data",)))
    sched = tr.strategy.schedule.runtime()
    with jax.set_mesh(mesh):
        st = tr.init(params)
        step = jax.jit(tr.step, static_argnums=(3,))
        for i in range(steps):
            st = step(st, batch, jax.random.key(7),
                      sched.is_exchange_step(i)).state
    return jax.device_get(st.params)

for legacy in (dict(schedule="every_step"),
               dict(schedule="local_k", local_k=4),
               dict(schedule="delayed", staleness_tau=2)):
    dq_legacy = DQConfig(optimizer="omd", compressor="qsgd8_linf",
                         exchange="sim", lr=0.05, worker_axes=("data",),
                         **legacy)
    st = Strategy.from_legacy(exchange="sim", worker_axes=("data",),
                              **legacy)
    dq_typed = DQConfig.from_strategy(st, optimizer="omd", lr=0.05)
    assert dq_legacy == dq_typed
    a, b = run(dq_legacy), run(dq_typed)
    for k in "xy":
        np.testing.assert_array_equal(a[k], b[k])
print("OK")
"""


@pytest.mark.multidevice
def test_legacy_vs_strategy_bit_exact_8dev(multidevice):
    out = multidevice(STRATEGY_8DEV_SCRIPT)
    assert "OK" in out


# --------------------------------------------------------------------------- #
# lr_mults group validation at DQGAN.init
# --------------------------------------------------------------------------- #
def test_lr_mults_unknown_group_raises():
    dq = DQConfig(optimizer="oadam", lr_mults=(("disc_", 5.0),))
    tr = DQGAN(field_fn=_field, dq=dq)
    with pytest.raises(ValueError, match=r"disc_.*not found"):
        tr.init({"gen": {"w": jnp.ones(3)}, "disc": {"w": jnp.ones(3)}})
    # valid group names pass
    ok = DQGAN(field_fn=_field, dq=DQConfig(optimizer="oadam",
                                            lr_mults=(("disc", 5.0),)))
    ok.init({"gen": {"w": jnp.ones(3)}, "disc": {"w": jnp.ones(3)}})


# --------------------------------------------------------------------------- #
# checkpoint: embedded strategy + fail-fast resume diff
# --------------------------------------------------------------------------- #
def test_checkpoint_strategy_guard(tmp_path):
    path = str(tmp_path / "ck.npz")
    st = Strategy(exchange=ExchangePlan(worker_axes=()),
                  schedule=Schedule.delayed(2))
    checkpoint.save(path, {"x": jnp.ones(3)}, step=7,
                    meta={"strategy": st.to_json()})
    assert checkpoint.read_meta(path)["strategy"] == st.to_json()
    assert checkpoint.latest_step(path) == 7
    checkpoint.verify_strategy(path, st)  # same strategy: fine
    other = st.evolve(schedule="every_step", staleness_tau=1)
    with pytest.raises(ValueError) as ei:
        checkpoint.verify_strategy(path, other)
    msg = str(ei.value)
    assert "schedule.kind: 'delayed' != 'every_step'" in msg
    assert "schedule.tau: 2 != 1" in msg
    # host-only fields (straggler profile) never block a resume — they
    # feed the wall-clock model, not the DQState layout
    checkpoint.verify_strategy(
        path, st.evolve(straggler_profile="heavy"))
    # pre-strategy checkpoints warn instead of failing
    checkpoint.save(path, {"x": jnp.ones(3)}, step=7)
    with pytest.warns(UserWarning, match="no embedded strategy"):
        checkpoint.verify_strategy(path, st)
    # the meta dict cannot clobber the reserved __meta__ record keys
    with pytest.raises(ValueError, match="reserved"):
        checkpoint.save(path, {"x": jnp.ones(3)}, step=7, meta={"step": 0})


# --------------------------------------------------------------------------- #
# CLI generation
# --------------------------------------------------------------------------- #
def test_cli_flags_resolve_to_strategy():
    import argparse

    from repro.strategy import add_strategy_args, strategy_from_args

    ap = argparse.ArgumentParser()
    add_strategy_args(ap)
    args = ap.parse_args(["--preset", "ssp_server", "--staleness-tau", "2",
                          "--no-error-feedback"])
    st = strategy_from_args(args, worker_axes=("data",))
    want = get_preset("ssp_server").evolve(
        staleness_tau=2, error_feedback=False, worker_axes=("data",))
    assert st == want

    args = ap.parse_args(["--strategy-json",
                          get_preset("low_bandwidth").to_json()])
    assert strategy_from_args(args) == get_preset("low_bandwidth")

    # boolean overrides work in BOTH directions over a preset base
    args = ap.parse_args(["--preset", "quantized_no_ef", "--error-feedback"])
    assert strategy_from_args(args).compression.error_feedback is True

    with pytest.raises(SystemExit):
        ap.parse_args(["--schedule", "bogus"])


def test_cli_kind_override_resets_companion_fields():
    """`--preset X --schedule Y` must not drag the preset's k/tau/budget
    onto a kind they are invalid for."""
    import argparse

    from repro.strategy import add_strategy_args, strategy_from_args

    ap = argparse.ArgumentParser()
    add_strategy_args(ap)
    # low_bandwidth is local_k(4): switching the kind drops K...
    st = strategy_from_args(
        ap.parse_args(["--preset", "low_bandwidth",
                       "--schedule", "every_step"]))
    assert st.schedule == Schedule.every_step()
    # ...but keeping the kind keeps the preset's K
    st = strategy_from_args(
        ap.parse_args(["--preset", "low_bandwidth",
                       "--schedule", "local_k"]))
    assert st.schedule.k == 4
    # ssp_server is delayed(4): kind switch drops tau; explicit tau wins
    st = strategy_from_args(
        ap.parse_args(["--preset", "ssp_server",
                       "--schedule", "local_k", "--local-k", "2"]))
    assert st.schedule == Schedule.local_k(2)
    # byte_budget carries budget_mb=1.0: switching plan drops the budget
    st = strategy_from_args(
        ap.parse_args(["--preset", "byte_budget",
                       "--comm-plan", "uniform"]))
    assert st.compression.plan == "uniform"
    assert st.compression.budget_mb == 0.0


def test_gate_refuses_fully_unmatched_baseline():
    """A sweep/schema change that shifts EVERY strategy hash must fail
    the gate, not silently gate nothing."""
    from benchmarks.run import check_sched_regression

    base = {"rows": [{"schedule": "delayed", "compressor": "8bit", "M": 8,
                      "strategy": "aaa111", "mean_step_s": 1.0,
                      "wire_mb": 10.0}]}
    shifted = {"rows": [{"schedule": "delayed", "compressor": "8bit",
                         "M": 8, "strategy": "ddd444",
                         "mean_step_s": 1.0, "wire_mb": 10.0}]}
    fails = check_sched_regression(shifted, base)
    assert len(fails) == 1 and "no current row matches" in fails[0]
