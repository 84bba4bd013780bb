"""Observability (repro.obs, DESIGN.md §11): the bit-exactness contract
(metrics="off" is the exact pre-obs step graph AND trajectory), the
empirical-δ telemetry against the analytic compressor bounds, the sink
schema, the per-bucket ledger accounting, and the report CLI."""
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import comm
from repro import obs
from repro.comm.planner import analytic_delta
from repro.configs.base import DQConfig
from repro.core import compressors as C
from repro.core.dqgan import DQGAN
from repro.core.error_feedback import compress_with_ef
from repro.models.gan import GANConfig, gan_field_fn, mlp_gan_init
from repro.obs import report as obs_report
from repro.strategy import (Compression, Observability, Strategy,
                            StrategyError)

KEY = jax.random.key(0)


# --------------------------------------------------------------------------- #
# MetricSpec registry + Observability component
# --------------------------------------------------------------------------- #
def test_metric_spec_lattice():
    off, wire, full = (obs.METRIC_SPECS[k] for k in ("off", "wire", "full"))
    assert not off.on
    assert wire.on and full.on
    # wire ⊂ full: every group wire measures, full measures too
    for f in ("moments", "delta", "ef_norms", "staleness"):
        assert not getattr(wire, f) or getattr(full, f)
    # metric_keys is the out_specs contract: stable, bucket-aware
    assert obs.metric_keys(off, 0) == ()
    assert obs.metric_keys(wire, 0) == ("delta_hat", "ef_e1_norm",
                                        "ef_e2_norm")
    assert "bucket_delta" in obs.metric_keys(wire, 3)
    assert obs.metric_keys(full, 2) == (
        "msg_mean", "msg_var", "bucket_mean", "bucket_var", "delta_hat",
        "bucket_delta", "ef_e1_norm", "ef_e2_norm", "staleness_hist")


def test_observability_validation():
    with pytest.raises(StrategyError, match="metrics"):
        Observability(metrics="everything")
    # δ̂ reads the materialized EF residual — needs EF on
    with pytest.raises(StrategyError, match="error_feedback"):
        Strategy(compression=Compression(error_feedback=False),
                 observability=Observability(metrics="wire"))
    # off composes with anything
    Strategy(compression=Compression(error_feedback=False))


def test_observability_excluded_from_identity_hash():
    """Turning telemetry on must not shift the structural identity —
    checkpoint guards and CI regression baselines key on short_hash()."""
    base = Strategy()
    for metrics in ("wire", "full"):
        st = Strategy(observability=Observability(metrics=metrics,
                                                  spans=True))
        assert st.short_hash() == base.short_hash()
        assert "observability" not in st.identity_dict()
    # ... but the exact serialization keeps it (round-trip fidelity)
    st = Strategy(observability=Observability(metrics="full"))
    assert Strategy.from_json(st.to_json()) == st
    # pre-obs 4-component JSON still parses (defaults to off)
    old = {k: v for k, v in json.loads(Strategy().to_json()).items()
           if k != "observability"}
    assert Strategy.from_json(json.dumps(old)).observability.metrics == "off"


# --------------------------------------------------------------------------- #
# collector + finalize numerics
# --------------------------------------------------------------------------- #
def test_collector_finalize_matches_numpy():
    spec = obs.METRIC_SPECS["full"]
    col = obs.Collector(spec, n_buckets=2)
    rng = np.random.default_rng(0)
    raws = [rng.normal(size=128).astype(np.float32),
            rng.normal(size=64).astype(np.float32)]
    errs = [0.1 * r for r in raws]
    for bid, (r, e) in enumerate(zip(raws, errs)):
        col.bucket(bid, jnp.asarray(r), jnp.asarray(r), jnp.asarray(e))
    sums = col.sums()
    # the step body supplies these (EF tree walk + schedule state)
    sums["e1_sq"], sums["e2_sq"] = obs.ef_norms_sq(
        {"w": {"e1": jnp.asarray(errs[0])}})
    sums["staleness_hist"] = obs.staleness_hist(jnp.zeros(()), 2)
    out = jax.device_get(obs.finalize(spec, sums, col.counts(),
                                      n_workers=1, n_buckets=2))
    np.testing.assert_allclose(out["ef_e1_norm"],
                               np.linalg.norm(errs[0]), rtol=1e-5)
    cat = np.concatenate(raws)
    np.testing.assert_allclose(out["msg_mean"], cat.mean(), rtol=1e-5)
    np.testing.assert_allclose(out["msg_var"], cat.var(), rtol=1e-4)
    np.testing.assert_allclose(out["bucket_mean"],
                               [r.mean() for r in raws], rtol=1e-5)
    np.testing.assert_allclose(out["bucket_var"],
                               [r.var() for r in raws], rtol=1e-4)
    # err = 0.1·op → δ̂ = 1 − 0.01 everywhere
    np.testing.assert_allclose(out["delta_hat"], 0.99, rtol=1e-5)
    np.testing.assert_allclose(out["bucket_delta"], [0.99, 0.99],
                               rtol=1e-5)


def test_staleness_hist_fixed_shape():
    h = jax.device_get(obs.staleness_hist(jnp.asarray([0., 1., 1., 5.]),
                                          bins=3))
    np.testing.assert_array_equal(h, [1.0, 2.0, 1.0])  # 5 → overflow bin


# --------------------------------------------------------------------------- #
# the bit-exactness contract
# --------------------------------------------------------------------------- #
def _mix_trainer(metrics, bucketed=True):
    cfg = GANConfig(name="mix", image_size=0, data_dim=2, latent_dim=16,
                    hidden=128)
    dq = DQConfig(optimizer="omd", compressor="qsgd8_linf", exchange="sim",
                  error_feedback=True, lr=1e-2, worker_axes=(),
                  comm_plan="uniform" if bucketed else "none",
                  bucket_mb=0.03, obs_metrics=metrics)
    return DQGAN(field_fn=gan_field_fn(cfg), dq=dq), cfg


def test_off_vs_full_trajectory_bit_exact():
    """The contract the whole subsystem hangs on: enabling telemetry
    changes nothing about the trajectory — params AND EF residuals are
    bit-identical after jitted steps."""
    finals = {}
    for metrics in ("off", "full"):
        tr, cfg = _mix_trainer(metrics)
        st = tr.init(mlp_gan_init(KEY, cfg))
        step = jax.jit(tr.step)
        for i in range(5):
            batch = {"real": jax.random.normal(jax.random.fold_in(KEY, i),
                                               (64, 2))}
            out = step(st, batch, jax.random.fold_in(KEY, 100 + i))
            st = out.state
        finals[metrics] = (jax.device_get(st), jax.device_get(out.metrics))
    st_off, m_off = finals["off"]
    st_full, m_full = finals["full"]
    assert "obs" not in m_off and "obs" in m_full
    a, b = jax.tree.leaves(st_off), jax.tree.leaves(st_full)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_off_hlo_identical_to_default_strategy():
    """metrics="off" must not merely be numerically close — the lowered
    step computation is the very graph an obs-free Strategy builds."""
    tr_off, cfg = _mix_trainer("off")
    dq = DQConfig(optimizer="omd", compressor="qsgd8_linf", exchange="sim",
                  error_feedback=True, lr=1e-2, worker_axes=(),
                  comm_plan="uniform", bucket_mb=0.03)
    tr_plain = DQGAN(field_fn=gan_field_fn(cfg), dq=dq)
    st = tr_off.init(mlp_gan_init(KEY, cfg))
    batch = {"real": jax.random.normal(KEY, (64, 2))}
    texts = [jax.jit(tr.step).lower(st, batch, KEY).as_text()
             for tr in (tr_off, tr_plain)]
    assert texts[0] == texts[1]


def test_full_metrics_do_not_add_retraces():
    """Telemetry rides inside the same trace: one compile per jit
    variant, whether metrics are on or off."""
    for metrics in ("off", "full"):
        tr, cfg = _mix_trainer(metrics)
        traces = []
        inner = tr.field_fn

        def counted(p, b, k):
            traces.append(1)
            return inner(p, b, k)

        tr = DQGAN(field_fn=counted, dq=tr.dq)
        st = tr.init(mlp_gan_init(KEY, cfg))
        step = jax.jit(tr.step)
        batch = {"real": jax.random.normal(KEY, (64, 2))}
        for i in range(4):
            st = step(st, batch, jax.random.fold_in(KEY, i)).state
        assert len(traces) == 1, (metrics, len(traces))


def test_single_device_obs_metrics_shapes():
    tr, cfg = _mix_trainer("full")
    st = tr.init(mlp_gan_init(KEY, cfg))
    batch = {"real": jax.random.normal(KEY, (64, 2))}
    m = jax.device_get(jax.jit(tr.step)(st, batch, KEY).metrics)
    o = m["obs"]
    B = tr._obs_n_buckets(st.params)
    assert B >= 1
    assert np.shape(o["bucket_var"]) == (B,)
    assert np.shape(o["bucket_delta"]) == (B,)
    assert np.shape(o["staleness_hist"]) == (tr._obs_bins(),)
    assert 0.9 < float(o["delta_hat"]) <= 1.0   # qsgd8 is ~0.9999-contractive
    assert float(o["ef_e1_norm"]) > 0.0
    assert float(o["msg_var"]) > 0.0


# --------------------------------------------------------------------------- #
# empirical δ̂ vs the analytic bounds (satellite d)
# --------------------------------------------------------------------------- #
def _measured_delta(comp, d=4096, rounds=8):
    num = den = 0.0
    for i in range(rounds):
        v = jax.random.normal(jax.random.fold_in(KEY, i), (d,))
        vhat = comp.roundtrip(v, jax.random.fold_in(KEY, 100 + i))
        num += float(jnp.sum((vhat - v) ** 2))
        den += float(jnp.sum(v * v))
    return 1.0 - num / den


def test_empirical_delta_matches_analytic():
    d = 4096
    # contractive quantizers: measured tracks the analytic curve
    assert abs(_measured_delta(C.get("qsgd8_linf"), d)
               - analytic_delta(C.get("qsgd8_linf"), d)) < 5e-3
    assert abs(_measured_delta(C.get("qsgd4_linf"), d)
               - analytic_delta(C.get("qsgd4_linf"), d)) < 0.05
    # sign-mean: δ = 2/π for Gaussian inputs
    assert abs(_measured_delta(C.get("sign"), d) - 2 / math.pi) < 0.02


def test_sign_delta_exact_identity():
    """Q(v) = (‖v‖₁/d)·sign(v) gives ‖v − Q(v)‖² = ‖v‖² − ‖v‖₁²/d
    exactly, so δ̂ = ‖v‖₁² / (d‖v‖²) per vector — the telemetry must
    reproduce the closed form, not just the Gaussian average."""
    comp = C.get("sign")
    v = jax.random.normal(KEY, (2048,))
    vhat = comp.roundtrip(v, KEY)
    measured = 1.0 - float(jnp.sum((vhat - v) ** 2) / jnp.sum(v * v))
    exact = float(jnp.sum(jnp.abs(v)) ** 2 / (v.size * jnp.sum(v * v)))
    assert abs(measured - exact) < 1e-5


def test_low_bit_quantizer_is_not_contractive():
    """qsgd2 (one stochastic level) is unbiased but NOT a δ-contraction —
    measured δ̂ goes negative while the planner's analytic_delta floors at
    1e-3. This gap is exactly what the δ̂ telemetry exists to surface."""
    measured = _measured_delta(C.get("qsgd2_linf"))
    assert measured < 0.0
    assert measured > -2.0                       # still variance-bounded
    assert analytic_delta(C.get("qsgd2_linf"), 4096) == pytest.approx(1e-3)


def test_ef_corrected_stream_error_decays():
    """With error feedback the time-averaged transmitted signal converges
    to the true gradient even under an aggressively biased compressor
    (top-25%); without EF the bias never washes out (satellite d)."""
    comp = C.TopK(frac=0.25)
    g = jax.random.normal(KEY, (512,))

    def stream_err(use_ef, T):
        e = jnp.zeros_like(g)
        tot = jnp.zeros_like(g)
        for t in range(T):
            k = jax.random.fold_in(KEY, t)
            if use_ef:
                _, sent, e = compress_with_ef(comp, g, e, k)
            else:
                sent = comp.roundtrip(g, k)
            tot = tot + sent
        return float(jnp.linalg.norm(tot / T - g) / jnp.linalg.norm(g))

    ef_short, ef_long = stream_err(True, 4), stream_err(True, 32)
    raw_long = stream_err(False, 32)
    assert ef_long < ef_short < raw_long
    assert ef_long < 0.1 and raw_long > 0.4


# --------------------------------------------------------------------------- #
# sink schema + backends (tentpole part 2)
# --------------------------------------------------------------------------- #
def test_schema_validation():
    ok = {"v": obs.SCHEMA_VERSION, "kind": "train_log", "step": 0,
          "loss": 1.0}
    obs.validate_event(ok)
    with pytest.raises(obs.SchemaError, match="version"):
        obs.validate_event({**ok, "v": 99})
    with pytest.raises(obs.SchemaError, match="unknown kind"):
        obs.validate_event({**ok, "kind": "vibes"})
    with pytest.raises(obs.SchemaError, match="missing"):
        obs.validate_event({"v": obs.SCHEMA_VERSION, "kind": "timing",
                            "step": 3})
    with pytest.raises(obs.SchemaError):
        obs.validate_event("not a dict")


def test_schema_v2_backward_compatible():
    """v1 files stay readable after the v2 bump; the v2-only kinds are
    refused when an event claims v1 (mislabeled writer, not an old
    file)."""
    assert obs.SCHEMA_VERSION == 2
    v1 = {"v": 1, "kind": "timing", "step": 0, "step_s": 1e-3,
          "interval_s": 1e-2}
    obs.validate_event(v1)                       # v1 read-compat
    prof = {"v": 2, "kind": "profile", "step0": 0, "n_steps": 4,
            "step_s": {"mean": 1e-3}}
    obs.validate_event(prof)
    calib = {"v": 2, "kind": "calibration", "bandwidth_Bps": 1e9,
             "latency_s": 1e-4}
    obs.validate_event(calib)
    for ev in (prof, calib):
        with pytest.raises(obs.SchemaError, match="requires schema v2"):
            obs.validate_event({**ev, "v": 1})
    with pytest.raises(obs.SchemaError, match="missing"):
        obs.validate_event({"v": 2, "kind": "profile", "step0": 0})


def test_jsonl_sink_roundtrip(tmp_path):
    path = str(tmp_path / "run.jsonl")
    with obs.make_sink(path, strategy_hash="abc123") as sink:
        sink.emit("run_meta", steps=10)
        sink.emit("train_log", step=0, loss=jnp.float32(0.5),
                  hist=jnp.arange(3))
    evs = obs.read_events(path)           # validates every line
    assert [e["kind"] for e in evs] == ["run_meta", "train_log"]
    assert all(e["strategy"] == "abc123" for e in evs)
    # device values were jsonified at emit time
    assert evs[1]["loss"] == 0.5 and evs[1]["hist"] == [0, 1, 2]


def test_sink_rejects_malformed_at_emit(tmp_path):
    sink = obs.make_sink(str(tmp_path / "x.jsonl"))
    with pytest.raises(obs.SchemaError):
        sink.emit("timing", step=1)       # missing step_s/interval_s
    sink.close()


def test_make_sink_mapping(tmp_path):
    assert isinstance(obs.make_sink(""), obs.StdoutSink)
    assert not obs.make_sink("").verbose
    assert obs.make_sink("stdout").verbose
    assert isinstance(obs.make_sink("null"), obs.NullSink)
    tee = obs.make_sink(str(tmp_path / "a.jsonl"), tee_stdout=True)
    assert isinstance(tee, obs.TeeSink)
    tee.close()


def test_stdout_sink_default_rendering(capsys):
    """The quiet default prints train_log rows exactly as the pre-obs
    launcher did (bare JSON, no envelope) and nothing else."""
    sink = obs.StdoutSink(strategy_hash="deadbeef")
    sink.emit("run_meta", steps=5)
    rec = {"step": 3, "loss": 0.25}
    sink.emit("train_log", **rec)
    out = capsys.readouterr().out
    assert out == json.dumps(rec) + "\n"
    obs.StdoutSink(verbose=True).emit("run_meta", steps=5)
    assert "# obs[run_meta]" in capsys.readouterr().out


# --------------------------------------------------------------------------- #
# CommLedger per-bucket accounting (satellite b)
# --------------------------------------------------------------------------- #
def _budget_ledger(M=8):
    cfg = GANConfig(name="mix", image_size=0, data_dim=2, latent_dim=16,
                    hidden=128)
    params = jax.eval_shape(lambda k: mlp_gan_init(k, cfg),
                            jax.random.key(0))
    shapes = jax.tree.map(lambda x: tuple(x.shape), params)
    comp = Compression(plan="delta_budget", budget_mb=0.024,
                       bucket_mb=0.0625, adaptive=True)
    layout, family = comp.build_family(shapes, None, M)
    led = comm.CommLedger.from_plan(layout, family.full, "two_phase", M,
                                    comp.compressor, family=family)
    return led, family, M


def test_ledger_per_bucket_rows():
    led, family, M = _budget_ledger()
    rows = led.per_bucket()
    assert len(rows) == len(family.full.assignments)
    for r, b in zip(rows, family.full.assignments):
        assert r["compressor"] == b.compressor
        assert r["elems"] == b.elems
        assert r["payload_bytes"] > 0 and r["wire_bytes"] > 0
        assert 0 < r["delta"] <= 1.0
        assert 0 < r["budget_share"] <= 1.0
    # shares account for the whole payload against the effective budget
    assert sum(r["budget_share"] for r in rows) == pytest.approx(
        family.full.payload_bytes / led.effective_budget(), abs=0.01)


def test_ledger_per_bucket_repriced_under_participation():
    """When n of M report, rows are priced under the family member the
    round actually selected, and the effective budget scales to B·M/n."""
    led, family, M = _budget_ledger()
    n = M // 2
    assert led.effective_budget(n) == pytest.approx(
        led.budget_bytes * M / n)
    rows_n = led.per_bucket(participants=n)
    sel = family.plan_for(n)
    assert [r["compressor"] for r in rows_n] == \
        [b.compressor for b in sel.assignments]
    # the freed budget buys finer bits somewhere (family is adaptive)
    assert sum(r["payload_bytes"] for r in rows_n) >= \
        sum(r["payload_bytes"] for r in led.per_bucket())


def test_ledger_summary_includes_buckets_and_budget():
    led, family, M = _budget_ledger()
    led.tick(5)
    s = led.summary()
    assert len(s["per_bucket"]) == len(family.full.assignments)
    assert s["budget_bytes"] == round(led.budget_bytes)
    assert s["budget_utilization"] == pytest.approx(
        sum(r["payload_bytes"] for r in s["per_bucket"])
        / led.effective_budget(), abs=0.01)
    json.dumps(s)                        # must stay JSON-serializable


# --------------------------------------------------------------------------- #
# report CLI (tentpole part 3)
# --------------------------------------------------------------------------- #
def _demo_events(tmp_path):
    path = str(tmp_path / "run.jsonl")
    with obs.make_sink(path, strategy_hash="cafe01") as sink:
        sink.emit("run_meta", steps=4, arch="dcgan32", n_workers=2,
                  obs_metrics="full")
        for step, (loss, ef) in enumerate([(0.5, 0.1), (0.4, 0.3)]):
            sink.emit("train_log", step=step, loss=loss)
            sink.emit("timing", step=step, step_s=0.01,
                      interval_s=0.02, steps_in_interval=2)
            sink.emit("obs_metrics", step=step, delta_hat=0.97,
                      bucket_delta=[0.97], ef_e1_norm=ef, ef_e2_norm=0.0,
                      staleness_hist=[2.0, 0.0], msg_mean=0.0,
                      msg_var=1e-3)
        sink.emit("comm_summary", wire_bytes_per_step=1000,
                  compression_ratio=4.0, sim_clock_s=1.0,
                  budget_bytes=4000, budget_utilization=0.25,
                  per_bucket=[{"bucket": 0, "compressor": "qsgd8_linf",
                               "bits": 8, "elems": 996,
                               "payload_bytes": 1000, "wire_bytes": 1000.0,
                               "delta": 0.9999, "budget_share": 0.25}])
    return path


def test_report_summarize_and_render(tmp_path):
    path = _demo_events(tmp_path)
    s = obs_report.summarize(obs.read_events(path))
    assert s["run"]["strategy"] == "cafe01"
    assert s["timing"]["step_s"]["n"] == 2
    [gap] = s["delta_gap"]
    assert gap["gap"] == pytest.approx(0.97 - 0.9999)
    assert s["obs"]["ef_e1"]["growth"] == pytest.approx(3.0)
    text = obs_report.render(s)
    for needle in ("cafe01", "assumed 0.9999", "measured 0.9700",
                   "25.0% utilization", "EF residual", "τ=0:2"):
        assert needle in text, (needle, text)


def test_report_cli_main(tmp_path, capsys):
    path = _demo_events(tmp_path)
    assert obs_report.main([path]) == 0
    assert "empirical δ̂ vs assumed δ" in capsys.readouterr().out
    assert obs_report.main([path, "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["run"]["arch"] == "dcgan32"


# --------------------------------------------------------------------------- #
# launcher end-to-end (single device; the 8-device acceptance run below)
# --------------------------------------------------------------------------- #
def test_train_launcher_writes_valid_sink(tmp_path):
    from repro.launch import train

    path = str(tmp_path / "run.jsonl")
    hist = train.main(["--arch", "dcgan32", "--smoke", "--steps", "4",
                       "--log-every", "2", "--comm-plan", "uniform",
                       "--obs-metrics", "full", "--obs-sink", path])
    assert hist
    evs = obs.read_events(path)          # schema-validates every event
    kinds = [e["kind"] for e in evs]
    assert kinds[0] == "run_meta"
    assert kinds.count("train_log") == kinds.count("timing") == \
        kinds.count("obs_metrics") == len(hist)
    assert kinds[-1] == "comm_summary"
    # every step's timing is a real synced measurement (satellite a):
    # the intervals partition the run
    timing = [e for e in evs if e["kind"] == "timing"]
    assert sum(e["steps_in_interval"] for e in timing) == 4
    assert all(0 < e["step_s"] <= e["interval_s"] for e in timing)
    om = [e for e in evs if e["kind"] == "obs_metrics"][-1]
    assert {"bucket_var", "bucket_delta", "delta_hat", "ef_e1_norm",
            "staleness_hist"} <= set(om)


# --------------------------------------------------------------------------- #
# 8-device invariance + acceptance (subprocess: forced host devices)
# --------------------------------------------------------------------------- #
INVARIANCE_8DEV_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.configs.base import DQConfig
from repro.core.dqgan import DQGAN
from repro.models.gan import GANConfig, mlp_gan_init, gan_field_fn
from repro.strategy import (Compression, ExchangePlan, Observability,
                            Participation, Schedule, Strategy)

mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
cfg = GANConfig(name="mix", image_size=0, data_dim=2, latent_dim=16,
                hidden=128)
key = jax.random.key(0)
params = mlp_gan_init(key, cfg)

def batch(i):
    return {"real": jax.random.normal(jax.random.fold_in(key, i), (64, 2))}

def run(spmd, metrics):
    strat = Strategy(
        compression=(Compression(plan="uniform", bucket_mb=0.03)
                     if spmd == "shard_map" else Compression()),
        exchange=ExchangePlan(
            kind="two_phase" if spmd == "shard_map" else "sim",
            spmd=spmd, worker_axes=("data",)),
        schedule=(Schedule.delayed(tau=2) if spmd == "shard_map"
                  else Schedule()),
        participation=Participation(fraction=0.5),
        observability=Observability(metrics=metrics))
    dq = DQConfig.from_strategy(strat, optimizer="omd", lr=1e-2)
    tr = DQGAN(field_fn=gan_field_fn(cfg), dq=dq, mesh=mesh,
               batch_spec=P(("data",)))
    with jax.set_mesh(mesh):
        step = jax.jit(tr.step, static_argnums=(3,))
        st = tr.init(params)
        for i in range(6):
            out = step(st, batch(i), jax.random.key(7), True)
            st = out.state
        return jax.device_get(st), jax.device_get(out.metrics)

for spmd in ("shard_map", "vmap"):
    st_off, m_off = run(spmd, "off")
    st_full, m_full = run(spmd, "full")
    assert "obs" not in m_off and "obs" in m_full, spmd
    a, b = jax.tree.leaves(st_off), jax.tree.leaves(st_full)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    o = m_full["obs"]
    hist = np.asarray(o["staleness_hist"])
    assert hist.sum() == 8.0, (spmd, hist)   # every worker lands in a bin
    assert float(o["ef_e1_norm"]) > 0.0, spmd
    assert -2.0 < float(o["delta_hat"]) <= 1.0, (spmd, o["delta_hat"])
print("OK")
"""


@pytest.mark.multidevice
def test_off_vs_full_bit_exact_8dev(multidevice):
    """Both SPMD paths, 8 workers, partial participation (+ bounded
    staleness on shard_map): telemetry never perturbs the trajectory."""
    assert "OK" in multidevice(INVARIANCE_8DEV_SCRIPT)


ACCEPTANCE_8DEV_SCRIPT = r"""
import os, tempfile
from repro.launch import train
from repro.obs import read_events
from repro.obs.report import render, summarize

path = os.path.join(tempfile.mkdtemp(), "run.jsonl")
hist = train.main(["--arch", "dcgan32", "--smoke", "--steps", "6",
                   "--log-every", "3", "--preset", "adaptive_budget",
                   "--obs-metrics", "full", "--obs-sink", path])
assert hist
evs = read_events(path)                 # schema-validates
om = [e for e in evs if e["kind"] == "obs_metrics"]
assert om, [e["kind"] for e in evs]
for e in om:
    for k in ("bucket_var", "bucket_delta", "delta_hat", "ef_e1_norm",
              "ef_e2_norm", "staleness_hist"):
        assert k in e, (k, sorted(e))
cs = [e for e in evs if e["kind"] == "comm_summary"][-1]
assert cs["per_bucket"] and cs["budget_utilization"] > 0
text = render(summarize(evs))
for needle in ("timing (synced)", "empirical δ̂ vs assumed δ",
               "utilization", "EF residual", "staleness histogram"):
    assert needle in text, (needle, text)
print("OK")
"""


@pytest.mark.multidevice
def test_adaptive_budget_acceptance_8dev(multidevice):
    """The ISSUE's acceptance run: metrics="full" on the adaptive_budget
    preset over 8 forced host devices fills the sink with per-bucket
    variance, empirical δ, EF norms and staleness histograms, and the
    report CLI renders all of it."""
    assert "OK" in multidevice(ACCEPTANCE_8DEV_SCRIPT)
