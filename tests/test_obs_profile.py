"""Step profiler (repro.obs.profile, DESIGN.md §12.1): window semantics,
event schema, launcher integration, and the bit-exactness contract —
profiling on/off must not shift the compiled step by one op."""
import glob
import gzip
import json
import os
import time

import jax
import pytest

from repro import obs
from repro.configs.base import DQConfig
from repro.core.dqgan import DQGAN
from repro.models.gan import GANConfig, gan_field_fn, mlp_gan_init
from repro.obs.profile import (
    DEFAULT_WINDOW,
    NullStepProfiler,
    StepProfiler,
    make_profiler,
)
from repro.strategy import Observability, Strategy, StrategyError

KEY = jax.random.key(0)
FIX = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


# --------------------------------------------------------------------------- #
# window semantics
# --------------------------------------------------------------------------- #
def test_window_fills_and_closes():
    p = StepProfiler(window=3)
    assert p.active and not p.done
    for i in range(5):                    # 2 extra records are ignored
        p.record_step(10 + i, 1e-3, exchanged=(i % 2 == 0))
    assert p.done
    assert len(p.step_walls) == 3
    assert p.first_step == 10
    assert p.exchange_steps == 2          # steps 10, 12


def test_phase_accumulates_only_while_active():
    p = StepProfiler(window=1)
    with p.phase("data"):
        time.sleep(0.001)
    p.record_step(0, 1e-3)
    with p.phase("data"):                 # window closed: no-op context
        time.sleep(0.001)
    assert p.phase_s["data"][1] == 1
    assert p.phase_s["data"][0] > 0


def test_phase_is_one_span_system(monkeypatch):
    """One context per host phase: it opens the phase's TraceAnnotation
    when spans are on and keeps its wall total while the window is open;
    a step's TraceAnnotation is a StepTraceAnnotation numbered by step."""
    opened = []

    class Annotation:
        def __init__(self, name, **kw):
            opened.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", Annotation)
    p = StepProfiler(window=1)
    with obs.step_span(7, True):
        with p.phase("dispatch", spans=True):
            pass
        with p.phase("sync"):             # spans off: timed, no span
            pass
    p.record_step(7, 1e-3)
    with p.phase("sync", spans=True):     # window closed: span only
        pass
    with obs.step_span(8, False):
        pass
    assert opened == [("repro.obs/train", {"step_num": 7}),
                      ("repro.obs/dispatch", {}), ("repro.obs/sync", {})]
    assert p.phase_s["dispatch"][1] == 1 and p.phase_s["sync"][1] == 1


def test_summary_payload():
    p = StepProfiler(window=4)
    for i, w in enumerate([3.0, 2e-3, 3e-3, 4e-3]):   # wall 0 = compile
        p.record_step(i, w)
    s = p.summary()
    assert s["step0"] == 0 and s["n_steps"] == 4
    assert s["step_s"]["min"] == 2e-3 and s["step_s"]["max"] == 3.0
    assert s["step_s"]["n"] == 4
    assert len(s["step_walls_s"]) == 4
    assert s["exchange_steps"] == 4
    assert "device_phases" not in s       # no HLO text given
    assert StepProfiler(window=2).summary() is None   # nothing recorded


def test_emit_is_idempotent_and_schema_valid(tmp_path):
    path = str(tmp_path / "prof.jsonl")
    sink = obs.JsonlFileSink(path, strategy_hash="abc")
    p = StepProfiler(window=2)
    p.record_step(0, 1e-3)
    p.record_step(1, 2e-3)
    ev = p.emit(sink)
    assert ev is not None and ev["kind"] == "profile" and ev["v"] == 2
    assert p.emit(sink) is None           # second emit: no-op
    sink.close()
    (read,) = obs.read_events(path)       # validates the schema
    assert read["n_steps"] == 2


def test_invalid_window():
    with pytest.raises(ValueError, match="window"):
        StepProfiler(window=0)


def test_make_profiler_factory():
    assert isinstance(make_profiler(False), NullStepProfiler)
    on = make_profiler(True)
    assert isinstance(on, StepProfiler) and on.window == DEFAULT_WINDOW
    assert make_profiler(True, window=7).window == 7


def test_null_profiler_surface(tmp_path):
    p = NullStepProfiler()
    with p.phase("dispatch"):
        pass
    with p.phase("sync", spans=True):
        pass
    p.record_step(0, 1e-3)
    assert p.done and not p.active and p.step_walls == []
    assert p.summary() is None
    assert p.emit(obs.NullSink()) is None
    assert p.device_phase_costs("anything") == {}


def test_device_phase_costs_from_fixture():
    """The committed optimized-HLO fixture carries the repro.obs scope
    metadata — the profiler's device-phase attribution reads it."""
    with gzip.open(os.path.join(FIX, "mix_every_step_8dev.hlo.txt.gz"),
                   "rt") as fh:
        txt = fh.read()
    dev = StepProfiler(window=1).device_phase_costs(txt)
    assert "exchange" in dev and dev["exchange"]["ops"] > 0
    assert dev["exchange"]["bytes"] > 0
    from repro.obs.tracing import DEVICE_PHASES
    assert set(dev) <= set(DEVICE_PHASES)


# --------------------------------------------------------------------------- #
# strategy surface
# --------------------------------------------------------------------------- #
def test_observability_profile_field_validated():
    assert Observability(profile=True).profile is True
    with pytest.raises(StrategyError, match="profile"):
        Observability(profile="yes")


def test_profile_outside_structural_identity():
    base = Strategy()
    prof = Strategy(observability=Observability(profile=True))
    assert prof.short_hash() == base.short_hash()
    assert "obs_profile" in base.legacy_fields()


def test_obs_profile_cli_flag():
    import argparse

    from repro import strategy as strategy_api
    ap = argparse.ArgumentParser()
    strategy_api.add_strategy_args(ap)
    args = ap.parse_args(["--obs-profile"])
    strat = strategy_api.strategy_from_args(args)
    assert strat.observability.profile is True


# --------------------------------------------------------------------------- #
# bit-exactness: profiling cannot touch the compiled step
# --------------------------------------------------------------------------- #
def test_profile_on_hlo_identical():
    cfg = GANConfig(name="mix", image_size=0, data_dim=2, latent_dim=16,
                    hidden=128)
    texts = []
    for profile in (False, True):
        dq = DQConfig(optimizer="omd", compressor="qsgd8_linf",
                      exchange="sim", error_feedback=True, lr=1e-2,
                      worker_axes=(), comm_plan="uniform", bucket_mb=0.03,
                      obs_profile=profile)
        tr = DQGAN(field_fn=gan_field_fn(cfg), dq=dq)
        st = tr.init(mlp_gan_init(KEY, cfg))
        batch = {"real": jax.random.normal(KEY, (64, 2))}
        texts.append(jax.jit(tr.step).lower(st, batch, KEY).as_text())
    assert texts[0] == texts[1]


PROFILE_HLO_8DEV_SCRIPT = r"""
import jax
from jax.sharding import AxisType, PartitionSpec as P
from repro.configs.base import DQConfig
from repro.core.dqgan import DQGAN
from repro.models.gan import GANConfig, mlp_gan_init, gan_field_fn
from repro.strategy import (Compression, ExchangePlan, Observability,
                            Schedule, Strategy)

mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
cfg = GANConfig(name="mix", image_size=0, data_dim=2, latent_dim=16,
                hidden=128)
key = jax.random.key(0)
params = mlp_gan_init(key, cfg)
batch = {"real": jax.random.normal(key, (64, 2))}

def lower(spmd, profile):
    strat = Strategy(
        compression=(Compression(plan="uniform", bucket_mb=0.03)
                     if spmd == "shard_map" else Compression()),
        exchange=ExchangePlan(
            kind="two_phase" if spmd == "shard_map" else "sim",
            spmd=spmd, worker_axes=("data",)),
        observability=Observability(profile=profile))
    dq = DQConfig.from_strategy(strat, optimizer="omd", lr=1e-2)
    tr = DQGAN(field_fn=gan_field_fn(cfg), dq=dq, mesh=mesh,
               batch_spec=P(("data",)))
    with jax.set_mesh(mesh):
        st = tr.init(params)
        return jax.jit(tr.step, static_argnums=(3,)).lower(
            st, batch, key, True).as_text()

for spmd in ("shard_map", "vmap"):
    assert lower(spmd, False) == lower(spmd, True), spmd
print("OK")
"""


@pytest.mark.multidevice
def test_profile_on_hlo_identical_8dev(multidevice):
    """Profiling is host-side only: the lowered step is byte-identical
    with profile on/off — 8 workers, both SPMD paths."""
    assert "OK" in multidevice(PROFILE_HLO_8DEV_SCRIPT)


# --------------------------------------------------------------------------- #
# launcher integration
# --------------------------------------------------------------------------- #
def test_train_launcher_emits_profile_event(tmp_path):
    from repro.launch import train

    path = str(tmp_path / "run.jsonl")
    hist = train.main(["--arch", "dcgan32", "--smoke", "--steps", "6",
                       "--log-every", "3", "--obs-sink", path,
                       "--profile-steps", "4", "--obs-spans"])
    assert hist
    evs = obs.read_events(path)
    (prof,) = [e for e in evs if e["kind"] == "profile"]
    assert prof["step0"] == 0 and prof["n_steps"] == 4
    assert prof["exchange_steps"] == 4          # every_step schedule
    assert prof["step_s"]["min"] > 0
    # one span system: each host phase is timed where its span opens, and
    # the step splits into the enqueue (dispatch) and the wait (sync)
    assert {"data", "dispatch", "sync"} <= set(prof["host_phases"])
    assert "step" not in prof["host_phases"]
    assert all(prof["host_phases"][k]["n"] == 4 for k in ("data",
                                                           "dispatch",
                                                           "sync"))
    # single-device sim path still lowers named scopes -> device phases
    assert prof.get("device_phases"), prof.keys()
    # the calibrate CLI consumes this file end-to-end
    from repro.obs import calibrate
    assert calibrate.main([path]) == 0


def test_train_launcher_trace_has_a_step_per_iteration(tmp_path):
    """--profile-trace-dir with spans: the captured trace holds one
    repro.obs/train step annotation per traced iteration, each with one
    dispatch and one sync inside it."""
    from jax.profiler import ProfileData

    from repro.launch import train

    trace_dir = str(tmp_path / "trace")
    train.main(["--arch", "dcgan32", "--smoke", "--steps", "6",
                "--log-every", "100", "--profile-steps", "5",
                "--profile-trace-dir", trace_dir, "--obs-spans"])
    (xplane,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
    names = [e.name for plane in ProfileData.from_file(xplane).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("repro.obs/")]
    steps = names.count("repro.obs/train")
    # the trace starts after the window's first step and stops inside its
    # last one, so the three steps between are whole
    assert steps >= 3
    assert names.count("repro.obs/dispatch") >= steps
    assert names.count("repro.obs/sync") >= steps


def test_train_launcher_obs_profile_flag_defaults_window(tmp_path):
    from repro.launch import train

    path = str(tmp_path / "run.jsonl")
    train.main(["--arch", "dcgan32", "--smoke", "--steps", "4",
                "--log-every", "2", "--obs-sink", path, "--obs-profile"])
    (prof,) = [e for e in read_profile(path)]
    # 4 steps < DEFAULT_WINDOW: the window never fills; the launcher
    # still emits the partial window at the end of the run
    assert prof["n_steps"] == 4


def read_profile(path):
    return [e for e in obs.read_events(path) if e["kind"] == "profile"]
