"""Structural HLO verification (repro.obs.hlo, DESIGN.md §12.2).

The committed fixtures under tests/fixtures/ are real optimized
(post-SPMD) HLO of the mix trainer's jitted step, lowered on 8 forced
host devices with spans on (regenerate with the snippet in
mix_8dev_expected.json's sibling docstring below) — they keep the
extraction + structure logic covered on single-device CI; the
@multidevice test re-derives everything live.

Regenerating the fixtures (after a deliberate step-graph change)::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python tests/fixtures/regen_mix_8dev.py
"""
import gzip
import json
import os

import pytest

from repro.obs import hlo as ohlo
from repro.strategy import Schedule

FIX = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


def _fixture(name: str) -> str:
    with gzip.open(os.path.join(FIX, name), "rt") as fh:
        return fh.read()


def _expected() -> dict:
    with open(os.path.join(FIX, "mix_8dev_expected.json")) as fh:
        return json.load(fh)


class _StubLedger:
    """Just enough CommLedger surface for byte_gap."""

    def __init__(self, wire, carried, n_workers):
        self.wire, self.carried, self.n_workers = wire, carried, n_workers

    def round_bytes(self, participants=None):
        return self.wire, self.carried

    def per_bucket(self, participants=None):
        return []


# --------------------------------------------------------------------------- #
# extraction against the committed fixtures
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("name", [
    "mix_every_step_8dev.hlo.txt.gz",
    "mix_local_k4_8dev.hlo.txt.gz",
    "mix_local_k4_mid_8dev.hlo.txt.gz",
    "mix_delayed_tau4_8dev.hlo.txt.gz",
    "mix_delayed_tau4_overlap_8dev.hlo.txt.gz",
])
def test_collective_summary_matches_recorded(name):
    txt = _fixture(name)
    assert ohlo.collective_summary(txt) == _expected()[name]["collectives"]


def test_scope_costs_survive_to_optimized_hlo():
    """The repro.obs named-scope metadata is present in the compiled
    step and scope_costs attributes real ops + bytes to each phase."""
    from repro.launch.hlo_analysis import scope_costs
    exp = _expected()
    for name, rec in exp.items():
        if not isinstance(rec, dict) or "scope_phases" not in rec:
            continue
        got = scope_costs(_fixture(name))
        assert {k: v["ops"] for k, v in got.items()} == rec["scope_phases"]
        # the exchange phase moves real bytes on exchange-step variants
        if "exchange" in rec["scope_phases"]:
            assert got["exchange"]["bytes"] > 0


def test_ring_parameters_delayed_fixture():
    txt = _fixture("mix_delayed_tau4_8dev.hlo.txt.gz")
    exp = _expected()
    rings = ohlo.ring_parameters(txt, 4)
    assert len(rings) == exp["mix_delayed_tau4_8dev.hlo.txt.gz"][
        "ring_params"]
    assert len(rings) >= exp["n_param_leaves"]
    assert all(4 in shp[:2] for shp in rings)


def test_entry_parameter_shapes_nonempty():
    shapes = ohlo.entry_parameter_shapes(
        _fixture("mix_every_step_8dev.hlo.txt.gz"))
    assert shapes, "no ENTRY parameters parsed"
    assert all(isinstance(s, tuple) for s in shapes)


# --------------------------------------------------------------------------- #
# the measured-vs-modeled byte gap
# --------------------------------------------------------------------------- #
def test_byte_gap_report():
    exp = _expected()
    led = _StubLedger(exp["ledger"]["wire_bytes_per_step"],
                      exp["ledger"]["carried_bytes_per_step"],
                      exp["ledger"]["n_workers"])
    gap = ohlo.byte_gap(_fixture("mix_every_step_8dev.hlo.txt.gz"), led)
    coll = exp["mix_every_step_8dev.hlo.txt.gz"]["collectives"]
    assert gap["hlo_bytes"] == sum(v["bytes"] for v in coll.values())
    assert gap["hlo_int8_bytes"] == sum(v["int8_bytes"]
                                        for v in coll.values())
    # transport factor 2(W-1)/W divided back out of the carried model
    W = exp["ledger"]["n_workers"]
    assert gap["modeled_result_bytes"] == pytest.approx(
        exp["ledger"]["carried_bytes_per_step"] / (2 * (W - 1) / W))
    assert gap["gap_ratio"] == pytest.approx(
        gap["hlo_bytes"] / gap["modeled_result_bytes"] - 1.0)
    # the recorded two_phase program moves the int8 codes twice (an
    # all-to-all in, an all-gather out), ~2x the carried model once its
    # transport factor is divided out — the gap is the point of the
    # report, assert it is surfaced
    assert gap["gap_ratio"] > 1.0


# --------------------------------------------------------------------------- #
# schedule-shaped structure
# --------------------------------------------------------------------------- #
def test_structure_every_step_fixture():
    rep = ohlo.assert_schedule_structure(
        Schedule(), _fixture("mix_every_step_8dev.hlo.txt.gz"))
    assert rep["exchange_class_totals"]["ops"] >= 1


def test_structure_local_k_fixture():
    rep = ohlo.assert_schedule_structure(
        Schedule.local_k(4),
        _fixture("mix_local_k4_8dev.hlo.txt.gz"),
        _fixture("mix_local_k4_mid_8dev.hlo.txt.gz"))
    # mid-round: scalar metric psums only — no payload-class bytes
    assert rep["midround_class_totals"]["int8_bytes"] == 0
    assert rep["midround_class_totals"]["bytes"] < \
        0.01 * rep["exchange_class_totals"]["bytes"]


def test_structure_delayed_fixture():
    exp = _expected()
    rep = ohlo.assert_schedule_structure(
        Schedule.delayed(tau=4),
        _fixture("mix_delayed_tau4_8dev.hlo.txt.gz"),
        n_param_leaves=exp["n_param_leaves"])
    assert len(rep["ring_parameters"]) >= exp["n_param_leaves"]


_NO_COLLECTIVE_HLO = """\
HloModule step

ENTRY %main (p0: f32[8,128]) -> f32[8,128] {
  %p0 = f32[8,128]{1,0} parameter(0)
  ROOT %add = f32[8,128]{1,0} add(%p0, %p0)
}
"""


def test_structure_violations_raise():
    # an exchange step with no collective at all is flagged ...
    with pytest.raises(AssertionError, match="no all-reduce-class"):
        ohlo.assert_schedule_structure(Schedule(), _NO_COLLECTIVE_HLO)
    # ... and a mid-round step moving the full exchange payload is the
    # accumulator leaking onto the wire
    ex = _fixture("mix_local_k4_8dev.hlo.txt.gz")
    rep = ohlo.check_schedule_structure(Schedule.local_k(4), ex,
                                        midround_txt=ex)
    assert not rep["ok"]
    assert any("quantized payload" in v or "leaking" in v
               for v in rep["violations"])
    # local_k without the mid-round variant cannot be verified
    rep = ohlo.check_schedule_structure(Schedule.local_k(4), ex)
    assert not rep["ok"]
    # delayed(τ) whose ring is absent from loop state is flagged
    with pytest.raises(AssertionError, match="ring"):
        ohlo.assert_schedule_structure(
            Schedule.delayed(tau=7),
            _fixture("mix_delayed_tau4_8dev.hlo.txt.gz"),
            n_param_leaves=12)


# --------------------------------------------------------------------------- #
# live: re-derive everything on 8 forced host devices (CI 8-dev tier)
# --------------------------------------------------------------------------- #
LIVE_8DEV_SCRIPT = r"""
import jax
from jax.sharding import AxisType, PartitionSpec as P
from repro.configs.base import DQConfig
from repro.core.dqgan import DQGAN
from repro.models.gan import GANConfig, mlp_gan_init, gan_field_fn
from repro.strategy import (Compression, ExchangePlan, Observability,
                            Schedule, Strategy)
from repro.obs import hlo as ohlo

mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
cfg = GANConfig(name="mix", image_size=0, data_dim=2, latent_dim=16,
                hidden=128)
params = mlp_gan_init(jax.random.key(0), cfg)
batch = {"real": jax.random.normal(jax.random.key(0), (64, 2))}

for schedule in (Schedule(), Schedule.local_k(4), Schedule.delayed(tau=4)):
    strat = Strategy(
        compression=Compression(plan="uniform", bucket_mb=0.03),
        exchange=ExchangePlan(kind="two_phase", spmd="shard_map",
                              worker_axes=("data",)),
        schedule=schedule,
        observability=Observability(spans=True))
    dq = DQConfig.from_strategy(strat, optimizer="omd", lr=1e-2)
    tr = DQGAN(field_fn=gan_field_fn(cfg), dq=dq, mesh=mesh,
               batch_spec=P(("data",)))
    with jax.set_mesh(mesh):
        st = tr.init(params)
        step = jax.jit(tr.step, static_argnums=(3,))
        ex = ohlo.compiled_text(step, st, batch, jax.random.key(7), True)
        mid = (ohlo.compiled_text(step, st, batch, jax.random.key(7),
                                  False)
               if schedule.kind == "local_k" else None)
    rep = ohlo.assert_schedule_structure(
        schedule, ex, mid, n_param_leaves=len(jax.tree.leaves(params)))
    gap = ohlo.byte_gap(ex, tr.comm_ledger(params))
    assert gap["hlo_bytes"] > 0 and gap["modeled_result_bytes"] > 0
    print(rep["schedule"], "ok")
print("OK")
"""


@pytest.mark.multidevice
def test_schedule_structure_live_8dev(multidevice):
    """The three schedule presets verified against freshly compiled HLO
    (not the fixtures) — the check the 8-device CI tier runs."""
    out = multidevice(LIVE_8DEV_SCRIPT)
    assert "OK" in out
    for frag in ("every_step", "local_k", "delayed"):
        assert frag in out
