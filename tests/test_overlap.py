"""Split-phase exchange API (DESIGN.md §13): start/finish handles, the
`exchange.overlap` lowering of delayed(τ), and the structural overlap
verification in repro.obs.hlo.

The bit-exactness lattice this file pins:

* overlap=False compiles to the SAME HLO as before the split-phase
  refactor (the committed 8-dev fixtures, modulo source-location debug
  metadata) — the sync path is start+immediate-finish with the
  historical op emission order;
* delayed(τ) with overlap=True is numerically BIT-EXACT to
  overlap=False — `Schedule.fold` hands the exchange a pending-ring
  head that never depends on this round's field output, so hoisting the
  start phase re-orders trace emission without changing any operand.

The vmap SPMD path has no overlap=True lowering (all workers share one
device; `StrategyError` below), so its lattice is just the
overlap=False identity, covered by the fixtures and existing tests.
"""
import gzip
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import DQConfig
from repro.core.dqgan import DQGAN
from repro.obs import hlo as ohlo
from repro.strategy import (
    Compression,
    ExchangePlan,
    Observability,
    Schedule,
    Strategy,
    StrategyError,
)
from repro.strategy.presets import get_preset

FIX = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
KEY = jax.random.key(0)


def _fixture(name: str) -> str:
    with gzip.open(os.path.join(FIX, name), "rt") as fh:
        return fh.read()


def _expected() -> dict:
    with open(os.path.join(FIX, "mix_8dev_expected.json")) as fh:
        return json.load(fh)


# --------------------------------------------------------------------------- #
# plan validation + surface
# --------------------------------------------------------------------------- #
def test_overlap_rejects_vmap():
    with pytest.raises(StrategyError, match=r"exchange\.overlap"):
        ExchangePlan(spmd="vmap", overlap=True)


def test_overlap_rejects_exact():
    with pytest.raises(StrategyError, match=r"exchange\.overlap"):
        ExchangePlan(kind="exact", overlap=True)


def test_overlap_requires_bool():
    with pytest.raises(StrategyError, match=r"exchange\.overlap"):
        ExchangePlan(overlap=1)


def test_schedule_overlappable():
    assert not Schedule().overlappable
    assert not Schedule.local_k(4).overlappable
    assert Schedule.delayed(1).overlappable
    assert Schedule.delayed(4).overlappable


def test_plan_owner_ef_replaces_kind_matching():
    assert ExchangePlan(kind="two_phase").owner_ef
    assert not ExchangePlan(kind="sim").owner_ef
    assert not ExchangePlan(kind="allgather").owner_ef
    from repro.core.exchange import plan_has_owner_ef, transport_factor
    assert plan_has_owner_ef({"strategy": "two_phase"})
    assert not plan_has_owner_ef({"strategy": "allgather"})
    # ring transport factor shared by the ledger and byte_gap
    assert transport_factor(8) == pytest.approx(2 * 7 / 8)
    assert ExchangePlan().transport_factor(8) == pytest.approx(2 * 7 / 8)
    assert transport_factor(1) == 0.0


def test_overlap_presets():
    assert get_preset("overlap").exchange.overlap
    assert get_preset("overlap").schedule.kind == "delayed"
    assert get_preset("ssp_server").exchange.overlap
    assert not get_preset("paper_dqgan").exchange.overlap


def test_overlap_in_json_roundtrip_and_hash():
    s_on = Strategy(exchange=ExchangePlan(overlap=True),
                    schedule=Schedule.delayed(2))
    s_off = Strategy(schedule=Schedule.delayed(2))
    assert Strategy.from_json(s_on.to_json()) == s_on
    assert "overlap" in s_on.to_dict()["exchange"]
    assert s_on.short_hash() != s_off.short_hash()


def test_cli_overlap_flag():
    import argparse
    from repro.strategy.cli import add_strategy_args, strategy_from_args
    ap = argparse.ArgumentParser()
    add_strategy_args(ap)
    s = strategy_from_args(ap.parse_args(
        ["--overlap", "--schedule", "delayed", "--staleness-tau", "2"]))
    assert s.exchange.overlap and s.schedule.tau == 2
    # a preset base can be negated back off
    s = strategy_from_args(ap.parse_args(["--preset", "overlap",
                                          "--no-overlap"]))
    assert not s.exchange.overlap


# --------------------------------------------------------------------------- #
# split-phase handles at the exchange-module level
# --------------------------------------------------------------------------- #
def test_exchange_leaf_shim_matches_split_phase():
    """The deprecated blocking spelling is exactly start+finish."""
    from repro.core import compressors as C
    from repro.core import exchange as X
    comp = C.get("qsgd8_linf")
    p = jnp.array(np.random.RandomState(0).randn(32), jnp.float32)
    plan = X.plan_leaf("sim", p.shape, None, 1)
    ef = {"e1": jnp.zeros_like(p)}
    h = X.start_exchange(comp, plan, p, ef, KEY, (), 1, True)
    assert isinstance(h, X.ExchangeHandle) and h.strategy == "sim"
    q1, st1 = X.finish_exchange(h)
    q2, st2 = X.exchange_leaf(comp, plan, p, ef, KEY, (), 1, True)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), st1, st2)


# --------------------------------------------------------------------------- #
# 1-device numerics: overlap on/off bit-exact for delayed(τ)
# --------------------------------------------------------------------------- #
A = jnp.array(np.linalg.qr(np.random.RandomState(3).randn(6, 6))[0],
              jnp.float32)


def _bilinear(params, batch, rng):
    del batch, rng
    x, y = params["x"], params["y"]
    return ({"x": A @ y, "y": -(A.T @ x)}, {"loss": x @ A @ y})


def _run_state(strategy, steps=6):
    dq = DQConfig.from_strategy(strategy, optimizer="omd", lr=0.05)
    tr = DQGAN(field_fn=_bilinear, dq=dq)
    st = tr.init({"x": jnp.ones(6), "y": jnp.ones(6)})
    step = jax.jit(tr.step, static_argnums=(3,))
    for _ in range(steps):
        st = step(st, None, KEY, True).state
    return jax.device_get(st)


@pytest.mark.parametrize("tau", [1, 2, 4])
def test_delayed_overlap_bitexact_1dev(tau):
    base = dict(schedule=Schedule.delayed(tau),
                exchange=ExchangePlan(worker_axes=()))
    off = _run_state(Strategy(**base))
    base["exchange"] = ExchangePlan(worker_axes=(), overlap=True)
    on = _run_state(Strategy(**base))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), off, on)


def test_delayed_overlap_bitexact_1dev_bucketed():
    base = dict(
        compression=Compression(plan="uniform", bucket_mb=0.001),
        schedule=Schedule.delayed(2))
    off = _run_state(Strategy(
        exchange=ExchangePlan(kind="two_phase", worker_axes=()), **base))
    on = _run_state(Strategy(
        exchange=ExchangePlan(kind="two_phase", worker_axes=(),
                              overlap=True), **base))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), off, on)


# --------------------------------------------------------------------------- #
# obs.profile.overlap_ratio
# --------------------------------------------------------------------------- #
def test_overlap_ratio():
    from repro.obs.profile import overlap_ratio
    r = overlap_ratio([1.0, 2.0, 3.0], [4.0, 5.0, 6.0], exchange_s=4.0)
    assert r["t_on_s"] == 2.0 and r["t_off_s"] == 5.0
    assert r["hidden_s"] == 3.0
    assert r["hidden_frac"] == pytest.approx(0.75)
    assert r["exposed_s"] == pytest.approx(1.0)
    # scalars accepted; hidden clamps at 0; frac clamps at 1
    assert overlap_ratio(5.0, 3.0)["hidden_s"] == 0.0
    assert overlap_ratio(1.0, 9.0, exchange_s=2.0)["hidden_frac"] == 1.0
    assert "hidden_frac" not in overlap_ratio(1.0, 2.0)
    with pytest.raises(ValueError):
        overlap_ratio([], [1.0])


def test_report_overlap_rows():
    from repro.obs import report
    t_c, t_ex = 2e-3, 1e-3

    def run(strategy, step_s):
        return [
            {"v": 2, "kind": "run_meta", "steps": 64, "n_workers": 8,
             "arch": "syn", "strategy_json": strategy.to_dict()},
            {"v": 2, "kind": "timing", "step": 10, "step_s": step_s,
             "interval_s": step_s * 10, "steps_in_interval": 10},
            {"v": 2, "kind": "comm_summary", "wire_bytes_per_step": 2e6},
        ]

    evs = (run(Strategy(), t_c + t_ex)
           + run(Strategy(schedule=Schedule.local_k(4)), t_c + t_ex / 4)
           + run(Strategy(schedule=Schedule.delayed(2)), t_c + t_ex)
           + run(Strategy(exchange=ExchangePlan(overlap=True),
                          schedule=Schedule.delayed(2)),
                 t_c + 0.3 * t_ex))
    s = report.summarize(evs)
    (row,) = s["overlap"]
    assert row["schedule"] == "delayed(tau=2)" and row["n_workers"] == 8
    assert row["hidden_s"] == pytest.approx(0.7 * t_ex)
    assert row["hidden_frac"] == pytest.approx(0.7)
    assert "% hidden" in report.render(s)
    # unpaired runs produce no rows
    assert "overlap" not in report.summarize(
        run(Strategy(schedule=Schedule.delayed(2)), t_c))


# --------------------------------------------------------------------------- #
# launch.mesh.enable_overlap_flags
# --------------------------------------------------------------------------- #
def test_enable_overlap_flags_unknown_platform():
    from repro.launch.mesh import enable_overlap_flags
    with pytest.raises(ValueError, match="unknown platform"):
        enable_overlap_flags("quantum")


def test_enable_overlap_flags_after_init_warns():
    from repro.launch.mesh import enable_overlap_flags
    jax.devices()  # force backend init in this process
    with pytest.warns(UserWarning, match="after jax backend init"):
        assert enable_overlap_flags("gpu") == ()


def test_enable_overlap_flags_subprocess():
    """Before backend init the flags land in XLA_FLAGS, idempotently,
    and the backend still boots with them."""
    script = (
        "import os; os.environ.pop('XLA_FLAGS', None)\n"
        "from repro.launch.mesh import enable_overlap_flags, "
        "OVERLAP_XLA_FLAGS\n"
        "added = enable_overlap_flags('cpu')\n"
        "assert added == OVERLAP_XLA_FLAGS['cpu'], added\n"
        "assert enable_overlap_flags('cpu') == ()\n"
        "for f in OVERLAP_XLA_FLAGS['cpu']:\n"
        "    assert f in os.environ['XLA_FLAGS']\n"
        "import jax; jax.devices()\n"
        "print('FLAGS_OK')\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(FIX.rstrip(os.sep)), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(env["PYTHONPATH"])
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "FLAGS_OK" in out.stdout


# --------------------------------------------------------------------------- #
# handcrafted async HLO: pairing, single-count bytes, independence
# --------------------------------------------------------------------------- #
_ASYNC_HLO = """\
HloModule step

ENTRY %main (p0: f32[8,128]) -> f32[64,128] {
  %p0 = f32[8,128]{1,0} parameter(0)
  %ag-start = (f32[8,128]{1,0}, f32[64,128]{1,0}) all-gather-start(%p0), dimensions={0}, metadata={op_name="jit(step)/repro.obs/exchange/ag"}
  %mul = f32[8,128]{1,0} multiply(%p0, %p0), metadata={op_name="jit(step)/repro.obs/field/mul"}
  %add = f32[8,128]{1,0} add(%mul, %p0), metadata={op_name="jit(step)/repro.obs/field/add"}
  %ag-done = f32[64,128]{1,0} all-gather-done(%ag-start)
  ROOT %out = f32[64,128]{1,0} copy(%ag-done)
}
"""

_UNMATCHED_HLO = """\
HloModule step

ENTRY %main (p0: f32[8,128]) -> f32[8,128] {
  %p0 = f32[8,128]{1,0} parameter(0)
  %ar-start = (f32[8,128]{1,0}, f32[8,128]{1,0}) all-reduce-start(%p0), to_apply=%sum
  ROOT %add = f32[8,128]{1,0} add(%p0, %p0)
}
"""

_TAINTED_HLO = """\
HloModule step

ENTRY %main (p0: f32[8,128]) -> f32[8,128] {
  %p0 = f32[8,128]{1,0} parameter(0)
  %grad = f32[8,128]{1,0} multiply(%p0, %p0), metadata={op_name="jit(step)/repro.obs/field/grad"}
  ROOT %ar = f32[8,128]{1,0} all-reduce(%grad), to_apply=%sum, metadata={op_name="jit(step)/repro.obs/exchange/ar"}
}
"""


def test_async_collective_pairs_handcrafted():
    rep = ohlo.async_collective_pairs(_ASYNC_HLO)
    assert rep["pairs"] == 1 and rep["unmatched_starts"] == 0
    # mul + add scheduled inside the start/done window
    assert rep["min_compute_between"] == 2
    assert rep["detail"][0]["op"] == "all-gather"

    rep = ohlo.async_collective_pairs(_UNMATCHED_HLO)
    assert rep["pairs"] == 0 and rep["unmatched_starts"] == 1
    assert ohlo.async_collective_pairs(_TAINTED_HLO)["pairs"] == 0


def test_async_pair_bytes_counted_once():
    """byte_gap's HLOAnalysis must count an async pair once (the
    destination half of the -start's aliasing tuple), not operand +
    destination + the -done reprint."""
    summ = ohlo.collective_summary(_ASYNC_HLO)
    assert summ == {"all-gather": {"count": 1.0,
                                   "bytes": 64 * 128 * 4,
                                   "int8_bytes": 0}}
    # sync spelling of the same transfer agrees
    sync = _TAINTED_HLO
    assert ohlo.collective_summary(sync)["all-reduce"]["bytes"] == \
        8 * 128 * 4


def test_exchange_field_independence_handcrafted():
    ok = ohlo.exchange_field_independence(_ASYNC_HLO)
    assert ok["ok"] and ok["exchange_collectives"] == 1
    assert not ok["tainted"]

    bad = ohlo.exchange_field_independence(_TAINTED_HLO)
    assert not bad["ok"] and bad["exchange_collectives"] == 1
    assert "depends on field op" in bad["tainted"][0]

    # without span metadata the check reports, not guesses
    plain = _ASYNC_HLO.replace("repro.obs/exchange", "x").replace(
        "repro.obs/field", "y")
    rep = ohlo.exchange_field_independence(plain)
    assert not rep["spans_present"] and not rep["ok"]


# --------------------------------------------------------------------------- #
# fixture-backed structure checks (the 8-dev CI tier's assertions,
# runnable on 1 device)
# --------------------------------------------------------------------------- #
def test_overlap_fixture_structure():
    exp = _expected()
    name = "mix_delayed_tau4_overlap_8dev.hlo.txt.gz"
    txt = _fixture(name)
    rep = ohlo.assert_schedule_structure(
        Schedule.delayed(tau=4), txt,
        n_param_leaves=exp["n_param_leaves"], overlap=True)
    indep = rep["overlap_independence"]
    assert indep["ok"] and not indep["tainted"]
    assert indep["exchange_collectives"] == \
        exp[name]["independence"]["exchange_collectives"]
    # the CPU-lowered fixture has sync collectives only: pairs==0 is
    # reported (GPU/TPU evidence), never a violation by itself
    assert rep["async_pairs"]["pairs"] == 0


def test_overlap_fixture_matches_nonoverlap_summary():
    """overlap=True re-orders trace emission but moves the same bytes
    through the same collectives as overlap=False."""
    exp = _expected()
    assert exp["mix_delayed_tau4_overlap_8dev.hlo.txt.gz"]["collectives"] \
        == exp["mix_delayed_tau4_8dev.hlo.txt.gz"]["collectives"]


def test_overlap_check_requires_delayed():
    rep = ohlo.check_schedule_structure(
        Schedule(), _fixture("mix_every_step_8dev.hlo.txt.gz"),
        overlap=True)
    assert not rep["ok"]
    assert any("only defined for the delayed" in v
               for v in rep["violations"])


def test_every_step_exchange_depends_on_field():
    """The blocking schedules fail independence BY CONSTRUCTION — the
    message is this round's gradient: the check separates overlappable
    dataflow from wishful thinking."""
    rep = ohlo.exchange_field_independence(
        _fixture("mix_every_step_8dev.hlo.txt.gz"))
    assert rep["spans_present"] and rep["exchange_collectives"] > 0
    assert rep["tainted"] and not rep["ok"]


def test_delayed_fixture_independent_even_without_overlap():
    """Independence is a schedule-dataflow property: delayed(τ) passes
    it with overlap=False too — overlap=True additionally re-orders
    issue order so a real async scheduler can exploit it."""
    rep = ohlo.exchange_field_independence(
        _fixture("mix_delayed_tau4_8dev.hlo.txt.gz"))
    assert rep["ok"] and not rep["tainted"]


# --------------------------------------------------------------------------- #
# live 8-device: HLO identity for overlap=False, bit-exactness for
# overlap=True, and the structural overlap check on fresh lowerings
# --------------------------------------------------------------------------- #
_COMMON_8DEV = r"""
import gzip, os, re
import jax
import numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.configs.base import DQConfig
from repro.core.dqgan import DQGAN
from repro.models.gan import GANConfig, mlp_gan_init, gan_field_fn
from repro.strategy import (Compression, ExchangePlan, Observability,
                            Schedule, Strategy)
from repro.obs import hlo as ohlo

FIX = %r
mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
cfg = GANConfig(name="mix", image_size=0, data_dim=2, latent_dim=16,
                hidden=128)
params = mlp_gan_init(jax.random.key(0), cfg)
batch = {"real": jax.random.normal(jax.random.key(0), (64, 2))}

def build(schedule, overlap=False, kind="two_phase", plan="uniform",
          fraction=1.0):
    from repro.strategy import Participation
    strat = Strategy(
        compression=Compression(plan=plan, bucket_mb=0.03),
        exchange=ExchangePlan(kind=kind, spmd="shard_map",
                              worker_axes=("data",), overlap=overlap),
        schedule=schedule,
        participation=Participation(fraction=fraction),
        observability=Observability(spans=True))
    dq = DQConfig.from_strategy(strat, optimizer="omd", lr=1e-2)
    return DQGAN(field_fn=gan_field_fn(cfg), dq=dq, mesh=mesh,
                 batch_spec=P(("data",)))
""" % FIX

IDENTITY_8DEV_SCRIPT = _COMMON_8DEV + r"""
# source locations are environment-dependent debug metadata (the
# fixtures are written without them); everything else must be
# byte-identical to the committed fixtures
def canon(t):
    return ohlo.strip_locations(t)

def fixture(name):
    with gzip.open(os.path.join(FIX, name), "rt") as fh:
        return fh.read()

for name, schedule in [("every_step", Schedule()),
                       ("local_k4", Schedule.local_k(4)),
                       ("delayed_tau4", Schedule.delayed(tau=4))]:
    tr = build(schedule)
    with jax.set_mesh(mesh):
        st = tr.init(params)
        step = jax.jit(tr.step, static_argnums=(3,))
        ex = ohlo.compiled_text(step, st, batch, jax.random.key(7), True)
        assert canon(ex) == canon(
            fixture("mix_%s_8dev.hlo.txt.gz" % name)), \
            "HLO drifted for " + name
        if name == "local_k4":
            mid = ohlo.compiled_text(step, st, batch, jax.random.key(7),
                                     False)
            assert canon(mid) == canon(
                fixture("mix_local_k4_mid_8dev.hlo.txt.gz"))
    print(name, "identical")

# fresh overlap=True lowering passes the structural check
tr = build(Schedule.delayed(tau=4), overlap=True)
with jax.set_mesh(mesh):
    st = tr.init(params)
    step = jax.jit(tr.step, static_argnums=(3,))
    ex = ohlo.compiled_text(step, st, batch, jax.random.key(7), True)
rep = ohlo.assert_schedule_structure(
    Schedule.delayed(tau=4), ex,
    n_param_leaves=len(jax.tree.leaves(params)), overlap=True)
assert rep["overlap_independence"]["ok"]
print("OK")
"""

BITEXACT_8DEV_SCRIPT = _COMMON_8DEV + r"""
def run(tr, steps=5):
    with jax.set_mesh(mesh):
        st = tr.init(params)
        step = jax.jit(tr.step, static_argnums=(3,))
        for i in range(steps):
            st = step(st, batch, jax.random.key(i), True).state
    return jax.device_get(st)

cases = [
    dict(schedule=Schedule.delayed(1), kind="sim", plan="none"),
    dict(schedule=Schedule.delayed(2), kind="allgather", plan="none"),
    dict(schedule=Schedule.delayed(4)),
    dict(schedule=Schedule.delayed(2), fraction=0.5),
]
for case in cases:
    off = run(build(overlap=False, **case))
    on = run(build(overlap=True, **case))
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        off, on)
    print("bitexact", sorted(case.items()))
print("OK")
"""


@pytest.mark.multidevice
def test_overlap_false_hlo_identity_8dev(multidevice):
    out = multidevice(IDENTITY_8DEV_SCRIPT)
    assert "OK" in out and out.count("identical") == 3


@pytest.mark.multidevice
def test_overlap_bitexact_8dev(multidevice):
    out = multidevice(BITEXACT_8DEV_SCRIPT)
    assert "OK" in out and out.count("bitexact") == 4
