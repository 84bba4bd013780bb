"""Round-adaptive compression (DESIGN.md §10): PlanFamily construction,
the participation-aware ledger, heterogeneous per-worker τ_m, and the
single-device adaptive training path (full-participation bit-exactness +
no retracing). The Hypothesis property tests live in
test_plan_family_props.py; the 8-device variants in the multidevice
subprocess tests of test_comm.py/test_checkpoint.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import comm
from repro.comm.planner import plan_comm, plan_family, quant_ladder
from repro.configs.base import DQConfig
from repro.core import compressors as C
from repro.core.dqgan import DQGAN
from repro.models.gan import GANConfig, gan_field_fn, mlp_gan_init
from repro.sched import seeded_tau_vector
from repro.strategy import (
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
# quant_ladder
# --------------------------------------------------------------------------- #
def test_quant_ladder_structures():
    assert quant_ladder("qsgd8_linf") == ["qsgd8_linf", "qsgd4_linf",
                                          "qsgd2_linf"]
    assert quant_ladder("qsgd8_block1024") == [
        "qsgd8_block1024", "qsgd4_block1024", "qsgd2_block1024"]
    assert quant_ladder("qsgd4_linf") == ["qsgd4_linf", "qsgd2_linf"]
    for bad in ("sign", "identity", "topk1", "qsgd8_l2", "qsgd8_block256"):
        with pytest.raises(ValueError):
            quant_ladder(bad)


def test_adaptive_compression_validation():
    with pytest.raises(StrategyError, match="compression.adaptive"):
        Compression(adaptive=True)
    with pytest.raises(StrategyError, match="compression.adaptive"):
        Compression(plan="uniform", adaptive=True)
    with pytest.raises(StrategyError, match="compression.compressor"):
        Compression(plan="delta_budget", budget_mb=1.0, adaptive=True,
                    compressor="sign")
    # valid spelling constructs (and the preset registry carries one)
    Compression(plan="delta_budget", budget_mb=1.0, adaptive=True)
    assert get_preset("adaptive_budget").compression.adaptive


# --------------------------------------------------------------------------- #
# PlanFamily construction (fixed cases; randomized Hypothesis variants in
# test_plan_family_props.py)
# --------------------------------------------------------------------------- #
def test_family_invariants_fixed_case():
    shapes = {"a": (300, 300), "b": (64,), "c": (200, 500), "d": (90000,)}
    M = 8
    layout = comm.build_layout(shapes, None, n_workers=M,
                               bucket_bytes=1 << 19)
    full = plan_comm(layout, "qsgd8_linf", "uniform").payload_bytes
    budget = full // 2
    fam = plan_family(layout, "qsgd8_linf", budget, M)
    assert len(fam.plans) == M
    bits = fam.bits_table()
    for n in range(1, M + 1):
        p = fam.plan_for(n)
        at_floor = all(b == 2 for b in bits[n - 1])
        assert p.payload_bytes <= fam.effective_budget(n) or at_floor
    for bid in range(len(layout.buckets)):
        col = [bits[n][bid] for n in range(M)]  # n increasing
        assert all(a >= b for a, b in zip(col, col[1:])), (bid, col)
    deltas = [fam.plan_for(n).min_delta for n in range(1, M + 1)]
    assert all(a >= b - 1e-12 for a, b in zip(deltas, deltas[1:])), deltas
    # the n = M member IS the static delta_budget plan (the bit-exactness
    # anchor for full-participation adaptive training)
    static = plan_comm(layout, "qsgd8_linf", "delta_budget",
                       budget_bytes=budget)
    assert fam.full.assignments == static.assignments


def test_family_diff_names_participation_count():
    shapes = {"w": (256, 256), "v": (64, 2048)}
    layout = comm.build_layout(shapes, None, n_workers=4,
                               bucket_bytes=1 << 16)
    full = plan_comm(layout, "qsgd8_linf", "uniform").payload_bytes
    a = plan_family(layout, "qsgd8_linf", full // 2, 4)
    b = plan_family(layout, "qsgd8_linf", full // 3, 4)
    assert a.diff(a) == []
    d = a.diff(b)
    assert d and any(s.startswith("plan_family[n=") for s in d)
    n_named = {int(s.split("[n=")[1].split("]")[0])
               for s in d if "[n=" in s}
    # the named counts are exactly the members whose sub-plans differ
    want = {n for n in range(1, 5)
            if a.plan_for(n).assignments != b.plan_for(n).assignments}
    assert n_named == want
    assert any("budget_bytes" in s for s in a.diff(b))


def test_traced_quant_matches_static_quant():
    """TracedQuant with a concrete levels scalar reproduces StochasticQuant
    bit-for-bit (same draws, same codes) — the dispatch path is the same
    arithmetic, selected by data."""
    for name in ("qsgd8_linf", "qsgd4_linf", "qsgd2_linf"):
        sq = C.get(name)
        tq = C.TracedQuant(jnp.float32(sq.levels), per_block=sq.per_block)
        v = 0.3 * jax.random.normal(KEY, (2048,))
        a = jax.jit(lambda v: sq.roundtrip(v, KEY))(v)
        b = jax.jit(lambda v: tq.roundtrip(v, KEY))(v)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_kernel_dynamic_levels_matches_static():
    from repro.kernels.quantize import quantize_ef_flat

    n = 4 * 1024
    g = 0.3 * jax.random.normal(KEY, (n,))
    e = 0.05 * jax.random.normal(jax.random.fold_in(KEY, 1), (n,))
    r = jax.random.uniform(jax.random.fold_in(KEY, 2), (n,))
    for lv in (127, 7, 1):
        cs, ss, es = quantize_ef_flat(g, e, r, levels=lv)
        cd, sd, ed = jax.jit(
            lambda g, e, r, l: quantize_ef_flat(g, e, r, levels=l)
        )(g, e, r, jnp.float32(lv))
        np.testing.assert_array_equal(np.asarray(cs), np.asarray(cd))
        np.testing.assert_allclose(np.asarray(ss), np.asarray(sd), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(es), np.asarray(ed), atol=1e-6)


# --------------------------------------------------------------------------- #
# participation-aware ledger
# --------------------------------------------------------------------------- #
def _mix_layout_family(M=8, frac=0.5):
    params = jax.eval_shape(
        lambda k: mlp_gan_init(k, GANConfig(name="mix", image_size=0,
                                            data_dim=2, latent_dim=16,
                                            hidden=128)), KEY)
    shapes = jax.tree.map(lambda x: tuple(x.shape), params)
    layout = comm.build_layout(shapes, None, n_workers=M,
                               bucket_bytes=1 << 16)
    full = plan_comm(layout, "qsgd8_linf", "uniform").payload_bytes
    fam = plan_family(layout, "qsgd8_linf", int(full * frac), M)
    return layout, fam


def test_ledger_bills_selected_plan_for_reporting_workers():
    M = 8
    layout, fam = _mix_layout_family(M)
    led = comm.CommLedger.from_plan(layout, fam.full, "two_phase", M,
                                    "qsgd8_linf", family=fam)
    full_w, _ = led.round_bytes()            # all M ship the full-M plan
    half_w, _ = led.round_bytes(4)           # 4 ship the n=4 plan
    # fleet-average: half the workers report, but each ships the finer
    # n=4 plan — strictly more than half the full-M bytes (the absent
    # workers' budget is re-spent), yet still within the fleet-average
    # byte budget B times the two_phase collective multiplier
    assert half_w > 0.5 * full_w
    bound = fam.budget_bytes * 2 * (M - 1) / M
    assert half_w <= bound * (1 + 1e-9)
    assert full_w <= bound * (1 + 1e-9)
    # the old conservative accounting (full-M plan for everyone) is gone:
    led_static = comm.CommLedger.from_plan(layout, fam.full, "two_phase",
                                           M, "qsgd8_linf")
    stat_half_w, _ = led_static.round_bytes(4)
    assert stat_half_w == pytest.approx(0.5 * full_w)
    # cumulative accounting follows the billed rounds
    led.tick(10, participants=4)
    assert led.cumulative_wire_bytes == pytest.approx(10 * half_w)
    assert led.summary()["participants"] == 4
    # full-participation ticks keep the legacy identity
    led2 = comm.CommLedger.from_plan(layout, fam.full, "two_phase", M,
                                     "qsgd8_linf", family=fam)
    led2.tick(10)
    assert led2.cumulative_wire_bytes == pytest.approx(
        10 * led2.wire_bytes_per_step)


# --------------------------------------------------------------------------- #
# heterogeneous per-worker τ_m
# --------------------------------------------------------------------------- #
def test_tau_vector_validation_and_seeding():
    with pytest.raises(StrategyError, match="tau_vector"):
        Schedule(kind="every_step", tau_vector=(1,))
    with pytest.raises(StrategyError, match="max"):
        Schedule.delayed(2, tau_vector=(1, 3))
    with pytest.raises(StrategyError, match="ints"):
        Schedule.delayed(2, tau_vector=(0, 2))
    s = Schedule.delayed_hetero((1, 3, 2))
    assert s.tau == 3 and s.tau_vector == (1, 3, 2)
    tv = seeded_tau_vector(4, 8, seed=3)
    assert tv == seeded_tau_vector(4, 8, seed=3)  # deterministic
    assert len(tv) == 8 and max(tv) == 4 and min(tv) >= 1
    # JSON round-trip carries the vector
    st2 = Strategy(schedule=Schedule.delayed_hetero(tv),
                   exchange=ExchangePlan(worker_axes=()))
    assert Strategy.from_json(st2.to_json()) == st2
    # mismatched length refuses at trainer init
    dq = DQConfig.from_strategy(st2, optimizer="omd")
    tr = DQGAN(field_fn=lambda p, b, k: (p, {}), dq=dq)
    with pytest.raises(ValueError, match="tau_vector"):
        tr.init({"x": jnp.ones(4)})


def test_tau_vector_pull_positions():
    """Worker m's wire head is ring slot τ−τ_m (the message it produced
    τ_m steps ago) and its staleness correction sums exactly its τ_m
    in-flight slots."""
    s = Schedule.delayed_hetero((3, 1, 2))
    ring = {"p": jnp.arange(3 * 4, dtype=jnp.float32).reshape(3, 4)}
    state = {"pending": ring, "versions": jnp.zeros((3,), jnp.int32)}
    for m, tau_m in enumerate((3, 1, 2)):
        buf, head = s.wire_head(state, jnp.int32(m))
        np.testing.assert_array_equal(np.asarray(head["p"]),
                                      np.asarray(ring["p"][3 - tau_m]))
        stale = s.staleness_correction(buf, "update", 1.0, jnp.int32(m))
        np.testing.assert_allclose(
            np.asarray(stale["p"]),
            np.asarray(ring["p"][3 - tau_m:].sum(axis=0)), rtol=1e-6)
        v = s.advance_version(jnp.int32(-1), jnp.int32(10), None,
                              jnp.int32(m))
        assert int(v) == 10 - tau_m


# --------------------------------------------------------------------------- #
# single-device adaptive training: bit-exact + no retracing
# --------------------------------------------------------------------------- #
def _mk_mix_trainer(adaptive, participation=1.0, budget_mb=0.033):
    cfg = GANConfig(name="mix", image_size=0, data_dim=2, latent_dim=16,
                    hidden=128)
    strat = Strategy(
        compression=Compression(plan="delta_budget", budget_mb=budget_mb,
                                adaptive=adaptive, bucket_mb=0.03),
        exchange=ExchangePlan(kind="sim", worker_axes=()),
        participation=Participation(fraction=participation))
    dq = DQConfig.from_strategy(strat, optimizer="omd", lr=1e-3)
    return DQGAN(field_fn=gan_field_fn(cfg), dq=dq), cfg


def test_adaptive_single_worker_bit_exact_and_single_trace():
    tr_a, cfg = _mk_mix_trainer(True)
    tr_s, _ = _mk_mix_trainer(False)
    params = mlp_gan_init(KEY, cfg)
    fam = tr_a._family(params)
    assert fam is not None and fam.full.assignments == \
        tr_s._comm(params)[1].assignments
    batch = {"real": jax.random.normal(KEY, (64, 2))}
    traces = []

    def run(tr):
        def counting(st, b, k):
            traces.append(1)
            return tr.step(st, b, k)
        st = tr.init(params)
        step = jax.jit(counting)
        for i in range(4):
            st = step(st, batch, jax.random.fold_in(KEY, i)).state
        return st

    sa = run(tr_a)
    n_traces_a = len(traces)
    ss = run(tr_s)
    assert n_traces_a == 1, "adaptive step retraced across rounds"
    for a, b in zip(jax.tree.leaves(sa.params), jax.tree.leaves(ss.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_adaptive_strategy_json_and_hash_roundtrip():
    st2 = get_preset("adaptive_budget")
    back = Strategy.from_json(st2.to_json())
    assert back == st2 and back.short_hash() == st2.short_hash()
    assert "compression.adaptive: True != False" in st2.diff(
        st2.evolve(comm_adaptive=False))


def test_list_presets_cli():
    import contextlib
    import io

    from repro.strategy.__main__ import main

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        assert main(["--list-presets"]) == 0
    out = buf.getvalue()
    assert "adaptive_budget" in out and "re-spent on finer bits" in out
    # every preset prints name + hash + one-line doc
    from repro.strategy import PRESETS
    assert all(name in out for name in PRESETS)


# --------------------------------------------------------------------------- #
# 8 devices: adaptive dispatch under real participation (subprocess)
# --------------------------------------------------------------------------- #
ADAPTIVE_8DEV_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.configs.base import DQConfig
from repro.core.dqgan import DQGAN
from repro.models.gan import GANConfig, mlp_gan_init, gan_field_fn
from repro.strategy import (Compression, ExchangePlan, Participation,
                            Strategy)

mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
cfg = GANConfig(name="mix", image_size=0, data_dim=2, latent_dim=16,
                hidden=128)
key = jax.random.key(0)
params = mlp_gan_init(key, cfg)

def mk(adaptive, participation, exchange="sim"):
    st = Strategy(
        compression=Compression(plan="delta_budget", budget_mb=0.033,
                                adaptive=adaptive, bucket_mb=0.03),
        exchange=ExchangePlan(kind=exchange, worker_axes=("data",)),
        participation=Participation(fraction=participation))
    dq = DQConfig.from_strategy(st, optimizer="omd", lr=1e-2)
    return DQGAN(field_fn=gan_field_fn(cfg), dq=dq, mesh=mesh,
                 batch_spec=P(("data",)))

def run(tr, steps=5):
    traces = []
    def counting(st, batch, k, do_ex):
        traces.append(1)
        return tr.step(st, batch, k, do_ex)
    with jax.set_mesh(mesh):
        st = tr.init(params)
        step = jax.jit(counting, static_argnums=(3,))
        for i in range(steps):
            batch = {"real": jax.random.normal(jax.random.fold_in(key, i),
                                               (64, 2))}
            st = step(st, batch, jax.random.key(7), True).state
    return jax.device_get(st), len(traces)

# full participation: adaptive == static bit-exactly (single-member
# selection -> the identical static compressor path)
tr_a, tr_s = mk(True, 1.0), mk(False, 1.0)
assert tr_a._family(params).full.assignments == \
    tr_s._comm(params)[1].assignments
sa, na = run(tr_a); ss, ns = run(tr_s)
for a, b in zip(jax.tree.leaves(sa.params), jax.tree.leaves(ss.params)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
assert na == ns == 1

# partial participation: the traced-gather dispatch runs, compiles ONCE
# across rounds, and produces finite params that DIFFER from static
# (finer bits for the reporting workers)
for exchange in ("sim", "two_phase"):
    tr_p = mk(True, 0.5, exchange)
    fam = tr_p._family(params)
    assert fam.n_distinct > 1, fam.describe()
    sp, nt = run(tr_p, steps=6)
    assert nt == 1, f"adaptive step retraced ({nt} traces)"
    leaves = jax.tree.leaves(sp.params)
    assert all(np.isfinite(np.asarray(l)).all() for l in leaves)
    sq, _ = run(mk(False, 0.5, exchange), steps=6)
    diff = sum(float(np.abs(np.asarray(a) - np.asarray(b)).sum())
               for a, b in zip(leaves, jax.tree.leaves(sq.params)))
    assert diff > 0, "adaptive plan selection had no effect"
print("OK")
"""


@pytest.mark.multidevice
def test_adaptive_dispatch_8dev(multidevice):
    out = multidevice(ADAPTIVE_8DEV_SCRIPT)
    assert "OK" in out


# checkpoint: a mid-run adaptive state (EF residuals shaped by rounds of
# different selected plans) must resume bit-exactly through the existing
# strategy.to_json guard
ADAPTIVE_RESUME_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np, tempfile, os
from jax.sharding import AxisType, PartitionSpec as P
from repro import checkpoint
from repro.configs.base import DQConfig
from repro.core.dqgan import DQGAN
from repro.models.gan import GANConfig, mlp_gan_init, gan_field_fn
from repro.strategy import (Compression, ExchangePlan, Participation,
                            Strategy)

mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
cfg = GANConfig(name="mix", image_size=0, data_dim=2, latent_dim=16,
                hidden=128)
key = jax.random.key(0)
params = mlp_gan_init(key, cfg)
strat = Strategy(
    compression=Compression(plan="delta_budget", budget_mb=0.033,
                            adaptive=True, bucket_mb=0.03),
    exchange=ExchangePlan(kind="two_phase", worker_axes=("data",)),
    participation=Participation(fraction=0.5))
dq = DQConfig.from_strategy(strat, optimizer="omd", lr=1e-2)
tr = DQGAN(field_fn=gan_field_fn(cfg), dq=dq, mesh=mesh,
           batch_spec=P(("data",)))
N = 3

def batch(i):
    return {"real": jax.random.normal(jax.random.fold_in(key, i), (64, 2))}

with jax.set_mesh(mesh):
    step = jax.jit(tr.step, static_argnums=(3,))
    st = tr.init(params)
    for i in range(2 * N):
        st = step(st, batch(i), jax.random.key(7), True).state
    full = jax.device_get(st)

    st = tr.init(params)
    for i in range(N):
        st = step(st, batch(i), jax.random.key(7), True).state
    path = os.path.join(tempfile.mkdtemp(), "adaptive.npz")
    checkpoint.save(path, st, step=N, meta={"strategy": strat.to_json()})

    # guard: the same strategy resumes; a different family refuses with
    # the field-level diff
    checkpoint.verify_strategy(path, strat)
    try:
        checkpoint.verify_strategy(path, strat.evolve(comm_adaptive=False))
        raise SystemExit("guard let a mismatched family resume")
    except ValueError as e:
        assert "compression.adaptive" in str(e), e

    st = checkpoint.restore(path, tr.init(params))
    assert int(jax.device_get(st.step)) == N
    for i in range(N, 2 * N):
        st = step(st, batch(i), jax.random.key(7), True).state
    resumed = jax.device_get(st)

fl, rl = jax.tree.leaves(full), jax.tree.leaves(resumed)
assert len(fl) == len(rl)
for a, b in zip(fl, rl):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("OK")
"""


@pytest.mark.multidevice
def test_adaptive_checkpoint_resume_8dev(multidevice):
    out = multidevice(ADAPTIVE_RESUME_SCRIPT)
    assert "OK" in out


# heterogeneous τ_m on 8 workers: per-worker staleness metrics + resume
TAU_VECTOR_8DEV_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.configs.base import DQConfig
from repro.core.dqgan import DQGAN
from repro.models.gan import GANConfig, mlp_gan_init, gan_field_fn
from repro.sched import seeded_tau_vector
from repro.strategy import ExchangePlan, Schedule, Strategy

mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
cfg = GANConfig(name="mix", image_size=0, data_dim=2, latent_dim=16,
                hidden=128)
key = jax.random.key(0)
params = mlp_gan_init(key, cfg)
tv = seeded_tau_vector(3, 8, seed=1)

def run(schedule, steps=6):
    st = Strategy(exchange=ExchangePlan(kind="sim", worker_axes=("data",)),
                  schedule=schedule)
    tr = DQGAN(field_fn=gan_field_fn(cfg),
               dq=DQConfig.from_strategy(st, optimizer="omd", lr=1e-2),
               mesh=mesh, batch_spec=P(("data",)))
    with jax.set_mesh(mesh):
        s = tr.init(params)
        step = jax.jit(tr.step, static_argnums=(3,))
        for i in range(steps):
            batch = {"real": jax.random.normal(jax.random.fold_in(key, i),
                                               (64, 2))}
            out = step(s, batch, jax.random.key(7), True)
            s = out.state
    return jax.device_get(s), jax.device_get(out.metrics)

# per-worker staleness metrics reflect the τ_m bound once warm
_, m = run(Schedule.delayed_hetero(tv))
assert m["staleness_max"] == max(tv), (m, tv)
assert abs(m["staleness_mean"] - np.mean(tv)) < 1e-6, (m, tv)

# a homogeneous tau_vector is bit-exact with the plain delayed schedule
a, _ = run(Schedule.delayed(2, tau_vector=(2,) * 8))
b, _ = run(Schedule.delayed(2))
for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
print("OK")
"""


@pytest.mark.multidevice
def test_tau_vector_8dev(multidevice):
    out = multidevice(TAU_VECTOR_8DEV_SCRIPT)
    assert "OK" in out
