"""Benchmark harness — one section per paper table/figure plus the kernel
micro-benchmarks. Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--quick]

Sections:
  convergence : paper Figs 2–3 — DQGAN vs CPOAdam vs CPOAdam-GQ quality
  speedup     : paper Fig 4 — time/step and speedup vs workers from the
                sched.clock wall-clock model (homogeneous workers), with
                the original purely-analytic rows kept under "analytic"
  compression : compressor micro-bench (throughput, ratio, measured δ)
  kernels     : Pallas fused quantize+EF + flash attention vs jnp oracle
  comm        : repro.comm wire telemetry — bytes/step (per-step, cumulative,
                achieved ratio) and two_phase sim-fallback counts, seed
                per-tensor planner vs bucketed, on dcgan32 + gemma-2b smoke
  overlap     : measured split-phase overlap — the jitted mix step
                wall-clocked with exchange.overlap on vs off for
                delayed(τ) over 8 (forced) host devices; writes
                experiments/overlap_measured.json, which sched/speedup
                embed under "overlap_measured" (opt-in, like
                comm_adaptive)
  sched       : repro.sched — speedup-vs-M per exchange schedule
                (every_step / local_k / delayed) × compressor (f32 / 8-bit)
                under a straggler profile, plus the bounded-staleness
                τ∈{1,2,4,8} convergence-vs-staleness-vs-wall-clock
                frontier on the mixture benchmark (experiments/sched.json)
  fsdp        : ZeRO memory/wire frontier (opt-in) — modeled per-device
                peak bytes and per-round wire bytes for replicated
                two_phase vs compressed fsdp_zero2/zero3 on the dcgan32
                parameter count; asserts zero-3 peak < replicated at
                M=8 (experiments/fsdp.json, gated via
                experiments/baselines/fsdp_quick.json)
  serve       : repro.serve — continuous-batching engine vs sequential
                tokens/s (the engine must win at batch >= 4), a seeded
                offered-QPS sweep (latency p50/p99, tokens/s, KV-block
                occupancy) on a virtual clock, and the deterministic
                serve model rows the regression gate checks
                (experiments/serve.json)
  roofline    : benchmarks.roofline over the experiments/dryrun/*.json
                records — one row per (arch × shape × mesh) with the
                three roofline terms and the dominant bottleneck, plus
                the regenerated experiments/roofline.md. Missing records
                are reported explicitly (the dry-run sweep needs the
                production meshes; see repro.launch.dryrun)

Regression gate (CI): ``--check-against experiments/baselines/sched_quick.json``
re-runs the sched wall-clock model with the baseline's recorded compute
time and parameter count (so the model is fully deterministic across
hosts) and fails the run when any (schedule, compressor, M) row or any
τ-frontier row regresses >10% in modeled seconds/step or wire bytes.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

from repro.launch.compile_cache import enable_compile_cache


def _rerun_on_host_devices(section: str, quick: bool):
    """Re-run one section in a child on 8 forced CPU host devices.

    Only a CPU parent may do this: a parent that has touched an
    accelerator holds it, and the child (forced onto the CPU) would
    quietly measure the host instead. So on a chip it refuses."""
    import subprocess

    if jax.default_backend() != "cpu":
        raise SystemExit(
            f"{section}: needs >= 4 devices, found {jax.device_count()} "
            f"{jax.default_backend()} device(s); it does not fall back to "
            "forced CPU host devices")
    print(f"# {section}: <4 devices — re-running with 8 forced host "
          "devices", flush=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    cmd = [sys.executable, "-m", "benchmarks.run", "--only", section] \
        + (["--quick"] if quick else [])
    subprocess.run(cmd, check=True, env=env)


def _timeit(fn, *args, iters=20, warmup=3):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


# run sink (repro.obs): when --obs-sink is given, every CSV row also
# lands in the structured event stream as a bench_row event (and
# bench_comm emits full comm_summary events), so CI can archive one
# JSONL artifact per benchmark run and `repro.obs report` can read it.
_SINK = None


def row(name, us, derived):
    print(f"{name},{us:.1f},{derived}", flush=True)
    if _SINK is not None:
        _SINK.emit("bench_row", name=name, us=round(us, 1),
                   derived=derived)


# --------------------------------------------------------------------------- #
def bench_convergence(quick: bool):
    """Paper Figs 2–3 analogue on the 2-D mixture benchmark."""
    from benchmarks.gan_common import train_mixture_gan

    steps = 400 if quick else 2000
    results = {}
    for method in ("CPOAdam", "CPOAdam-GQ", "DQGAN", "DQGAN-noEF"):
        t0 = time.perf_counter()
        final, _, _ = train_mixture_gan(method, steps=steps)
        us = (time.perf_counter() - t0) / steps * 1e6
        results[method] = final
        row(f"convergence/{method}", us,
            f"modes={final['modes']}/8 hq={final['hq_frac']} fid={final['fid']}")
    with open("experiments/convergence.json", "w") as f:
        json.dump({"steps": steps, "results": results}, f, indent=1)
    return results


# --------------------------------------------------------------------------- #
_COMPUTE_TIME_CACHE = {}


def _dcgan_compute_time(quick: bool):
    """(t_compute_seconds, d): measured DCGAN field time on this host and
    the exchanged parameter count — the inputs every speed model shares.
    Memoized so sched + speedup sections of one run agree (and the model
    only builds/compiles once)."""
    if quick in _COMPUTE_TIME_CACHE:
        return _COMPUTE_TIME_CACHE[quick]
    from repro.models.gan import GANConfig, dcgan_init, gan_field_fn

    cfg = GANConfig(image_size=32, channels=3, latent_dim=128,
                    base_width=32 if quick else 64)
    key = jax.random.key(0)
    params = dcgan_init(key, cfg)
    d = sum(x.size for x in jax.tree.leaves(params))
    field = jax.jit(gan_field_fn(cfg))
    batch = {"real": jax.random.normal(key, (64, 32, 32, 3))}
    t_compute_us = _timeit(lambda: field(params, batch, key), iters=5)
    _COMPUTE_TIME_CACHE[quick] = (t_compute_us / 1e6, d)
    return _COMPUTE_TIME_CACHE[quick]


# --------------------------------------------------------------------------- #
# shared sweep definitions (repro.strategy): the schedule × wire points
# are Strategy OBJECTS — one spelling for the speedup and sched sections,
# and the structural identity (strategy.short_hash()) the regression gate
# keys baselines by.
# --------------------------------------------------------------------------- #
def _wire_strategies():
    from repro.strategy import Strategy

    return {
        # f32 on the wire (exact averaging) vs int8 two-phase collectives
        "f32": Strategy.from_legacy(exchange="exact"),
        "8bit": Strategy.from_legacy(exchange="two_phase"),
    }


def _sched_strategies(K):
    from repro.strategy import Schedule, Strategy

    return (
        ("every_step", Strategy()),
        ("local_k", Strategy(schedule=Schedule.local_k(K))),
        ("delayed", Strategy(schedule=Schedule.delayed())),
    )


def sweep_points(K):
    """The full schedule × compressor sweep as composed Strategy objects:
    yields (schedule_label, wire_label, Strategy)."""
    import dataclasses

    for sname, s_st in _sched_strategies(K):
        for cname, w_st in _wire_strategies().items():
            yield sname, cname, dataclasses.replace(
                s_st, exchange=w_st.exchange)


def _wire_models(d):
    """Per-worker bytes of ONE exchange by wire label."""
    return {name: (lambda M, st=st: st.modeled_wire_bytes(d, M))
            for name, st in _wire_strategies().items()}


def bench_speedup(quick: bool):
    """Paper Fig 4 analogue, regenerated from the sched.clock wall-clock
    model: per-step time and speedup vs workers, f32 vs 8-bit, for each
    exchange schedule over homogeneous workers. The original purely
    analytic rows (T(M) = T₁/M + T_comm, no latency/overlap model) are
    kept under an "analytic" sub-key for comparison."""
    from repro import sched as S

    t_compute, d = _dcgan_compute_time(quick)
    wire = _wire_models(d)
    Ms = (1, 2, 4, 8, 16, 32)

    # -- the seed's analytic model, unchanged ------------------------------- #
    link_bw = 1e9   # bytes/s per worker link (10GbE PS uplink, the
    # regime of the paper's Fig 4; at NVLink speeds compression is moot)
    analytic = []
    for M in Ms:
        t_comm_f32 = wire["f32"](max(M, 2)) / link_bw if M > 1 else 0.0
        t_comm_q8 = wire["8bit"](max(M, 2)) / link_bw if M > 1 else 0.0
        tf32 = t_compute / M + t_comm_f32
        tq8 = t_compute / M + t_comm_q8
        analytic.append({"M": M, "speedup_f32": round(t_compute / tf32, 2),
                         "speedup_8bit": round(t_compute / tq8, 2)})

    # -- schedule-aware wall-clock model (homogeneous workers) -------------- #
    profile = S.get_profile("none")
    steps = SCHED_MODEL_STEPS[quick]
    base = S.baseline_mean_step(profile, steps, t_compute)
    rows = []
    for sname, strat in _sched_strategies(K=4):
        sch = strat.schedule.runtime()
        per = {}
        for cname, bfn in wire.items():
            per[cname] = {r["M"]: r for r in S.speedup_vs_M(
                sch, profile, Ms, steps, t_compute,
                lambda M, b=bfn: b(max(M, 2)), base=base)}
        for M in Ms:
            rows.append({"M": M, "schedule": sname,
                         "speedup_f32": round(per["f32"][M]["speedup"], 2),
                         "speedup_8bit": round(per["8bit"][M]["speedup"], 2),
                         "step_s_f32": per["f32"][M]["mean_step_s"],
                         "step_s_8bit": per["8bit"][M]["mean_step_s"]})
            row(f"speedup/{sname}/M={M}",
                per["f32"][M]["mean_step_s"] * 1e6,
                f"f32={rows[-1]['speedup_f32']}x "
                f"8bit={rows[-1]['speedup_8bit']}x")
    out = {"d": d, "t_compute_us": t_compute * 1e6,
           "model": "sched.clock (profile=none, LinkModel default)",
           "steps": steps,
           "rows": rows,
           "analytic": {"model": "T(M) = T1/M + bytes/bw",
                        "rows": analytic}}
    measured = _load_overlap_measured()
    if measured:
        out["overlap_measured"] = measured
    with open("experiments/speedup.json", "w") as f:
        json.dump(out, f, indent=1)
    return rows


# --------------------------------------------------------------------------- #
# measured split-phase overlap (exchange.overlap on vs off wall clocks)
# --------------------------------------------------------------------------- #
OVERLAP_TAUS = (1, 2, 4)
OVERLAP_M = 8


def bench_overlap(quick: bool):
    """Measured — not modeled — split-phase overlap: the mix trainer's
    jitted step wall-clocked with ``exchange.overlap`` on vs off for
    ``delayed(τ)``, τ ∈ {1, 2, 4}, over 8 workers (two_phase /
    shard_map, spans on). Writes experiments/overlap_measured.json;
    bench_sched and bench_speedup embed the rows under
    ``overlap_measured`` so the committed artifacts carry the measured
    overlap next to the modeled speedup rows.

    ``hidden_s`` = p50(off) − p50(on) is the step wall the split-phase
    lowering removed. On CPU backends XLA emits no async collectives,
    so any hidden time there comes from scheduler reordering only — the
    ≥50%-hidden expectation is a GPU/TPU-class statement (DESIGN.md
    §13); the artifact records the platform so readers can tell."""
    from jax.sharding import AxisType, PartitionSpec as P

    from repro.configs.base import DQConfig
    from repro.core.dqgan import DQGAN
    from repro.models.gan import GANConfig, gan_field_fn, mlp_gan_init
    from repro.obs.profile import overlap_ratio
    from repro.strategy import (Compression, ExchangePlan, Observability,
                                Schedule, Strategy)

    if jax.device_count() < 4:
        # a single device has no wire to hide
        _rerun_on_host_devices("overlap", quick)
        return _load_overlap_measured()

    M = min(jax.device_count(), OVERLAP_M)
    mesh = jax.make_mesh((M,), ("data",), axis_types=(AxisType.Auto,))
    cfg = GANConfig(name="mix", image_size=0, data_dim=2, latent_dim=16,
                    hidden=128)
    params = mlp_gan_init(jax.random.key(0), cfg)
    batch = {"real": jax.random.normal(jax.random.key(0), (64, 2))}
    warm, n_steps = (3, 24) if quick else (5, 96)

    def walls(tau, overlap):
        strat = Strategy(
            compression=Compression(plan="uniform", bucket_mb=0.03),
            exchange=ExchangePlan(kind="two_phase", spmd="shard_map",
                                  worker_axes=("data",), overlap=overlap),
            schedule=Schedule.delayed(tau=tau),
            observability=Observability(spans=True))
        dq = DQConfig.from_strategy(strat, optimizer="omd", lr=1e-2)
        tr = DQGAN(field_fn=gan_field_fn(cfg), dq=dq, mesh=mesh,
                   batch_spec=P(("data",)))
        out = []
        with jax.set_mesh(mesh):
            st = tr.init(params)
            step = jax.jit(tr.step, static_argnums=(3,))
            for i in range(warm + n_steps):
                t0 = time.perf_counter()
                res = jax.block_until_ready(
                    step(st, batch, jax.random.key(i), True))
                st = res.state
                if i >= warm:
                    out.append(time.perf_counter() - t0)
        return out

    rows = []
    for tau in OVERLAP_TAUS:
        w_off = walls(tau, False)
        w_on = walls(tau, True)
        r = overlap_ratio(w_on, w_off)
        r.update({"tau": tau, "n_workers": M, "steps": n_steps,
                  "hidden_frac_step": (round(r["hidden_s"] / r["t_off_s"], 4)
                                       if r["t_off_s"] else 0.0)})
        rows.append(r)
        row(f"overlap/tau={tau}", r["t_on_s"] * 1e6,
            f"off={r['t_off_s'] * 1e6:.0f}us "
            f"hidden={r['hidden_s'] * 1e6:.0f}us "
            f"({r['hidden_frac_step'] * 100:.1f}% of step)")
    out = {"platform": jax.devices()[0].platform, "n_workers": M,
           "steps": n_steps,
           "note": ("hidden_s = p50(overlap=False) - p50(overlap=True) "
                    "step wall, measured on the recorded platform; CPU "
                    "XLA emits no async collectives, so the >=50%-hidden "
                    "expectation applies to GPU/TPU backends"),
           "rows": rows}
    with open("experiments/overlap_measured.json", "w") as f:
        json.dump(out, f, indent=1)
    return out


def _load_overlap_measured():
    """The last `--only overlap` artifact, if one has been generated —
    embedded verbatim into sched.json / speedup.json so the measured
    overlap rows travel with the modeled ones."""
    try:
        with open("experiments/overlap_measured.json") as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


# --------------------------------------------------------------------------- #
# simulated steps per tier — the gate refuses cross-tier comparisons (wire
# bytes scale with steps), so this mapping is shared with main()'s check
SCHED_MODEL_STEPS = {True: 64, False: 256}


def bench_sched(quick: bool, model_inputs=None, convergence: bool = True,
                out_path: str = "experiments/sched.json"):
    """repro.sched: simulated speedup-vs-M per exchange schedule ×
    compressor under the 'mild' straggler profile, plus the bounded-
    staleness τ∈{1,2,4,8} frontier — server-dataflow wall clock AND real
    mixture-GAN convergence under delayed(τ). The acceptance
    inequalities — local_k and delayed strictly cheaper per step than
    every_step once M ≥ 4, cumulative wire bytes monotone over the τ
    sweep — are asserted, not just reported.

    ``model_inputs`` = (t_compute_seconds, d) overrides the measured
    DCGAN field time, making every wall-clock number deterministic —
    the ``--check-against`` regression gate passes the baseline's
    recorded values here so CI hosts of different speeds compare equal
    models. ``convergence=False`` skips the frontier's mixture-GAN
    training (gate mode: convergence metrics are never gated, so the
    CI run keeps only the deterministic model)."""
    from benchmarks.gan_common import train_mixture_gan

    from repro import sched as S

    t_compute, d = model_inputs or _dcgan_compute_time(quick)
    wire = _wire_models(d)
    profile = S.get_profile("mild")
    K = 4
    steps = SCHED_MODEL_STEPS[quick]
    Ms = (1, 2, 4, 8, 16, 32)
    # The M=1 baseline is schedule- and compressor-independent (no comm):
    # simulate it ONCE here; speedup_vs_M reuses it both as the reference
    # and as the Ms[0] row (the quick tier previously simulated it twice
    # per schedule × compressor sweep).
    base = S.baseline_mean_step(profile, steps, t_compute)
    rows = []
    for sname, cname, strat in sweep_points(K):
        sch = strat.schedule.runtime()
        bfn = wire[cname]
        for r in S.speedup_vs_M(sch, profile, Ms, steps, t_compute,
                                lambda M, b=bfn: b(max(M, 2)),
                                base=base):
            wire_mb = (bfn(max(r["M"], 2)) * r["n_exchanges"] / 1e6
                       if r["M"] > 1 else 0.0)
            r.update({"schedule": sname, "compressor": cname,
                      "strategy": strat.short_hash(),
                      "wire_mb": round(wire_mb, 3)})
            rows.append(r)
            row(f"sched/{sname}/{cname}/M={r['M']}",
                r["mean_step_s"] * 1e6,
                f"speedup={r['speedup']:.2f}x "
                f"t_ex={r['t_exchange_s']*1e6:.0f}us "
                f"exchanges={r['n_exchanges']}")

    def mean_step(s, c, M):
        return next(r["mean_step_s"] for r in rows
                    if r["schedule"] == s and r["compressor"] == c
                    and r["M"] == M)

    for c in ("f32", "8bit"):
        for M in (4, 8, 16, 32):
            assert mean_step("local_k", c, M) < mean_step("every_step", c, M)
            assert mean_step("delayed", c, M) < mean_step("every_step", c, M)

    # ---- bounded-staleness frontier: τ vs wall clock vs convergence ------- #
    taus = (1, 2, 4, 8)
    M_f = 8
    conv_steps = 300 if quick else 1500
    frontier = []
    cum_wire_mb = 0.0
    for tau in taus:
        strat_tau = _wire_strategies()["8bit"].evolve(
            schedule="delayed", staleness_tau=tau)
        sim = S.time_per_step(strat_tau.schedule.runtime(), profile, M_f,
                              steps, t_compute, wire["8bit"](M_f),
                              dataflow="server")
        wire_mb = wire["8bit"](M_f) * sim["n_exchanges"] / 1e6
        cum_wire_mb += wire_mb
        f_row = {
            # clock_M labels the wall-clock/wire MODEL only; the
            # convergence run below is single-worker (sim-compressed, the
            # staleness effect isolated from worker averaging)
            "tau": tau, "clock_M": M_f,
            "strategy": strat_tau.short_hash(),
            "mean_step_s": sim["mean_step_s"],
            "total_s": sim["total_s"],
            "n_exchanges": sim["n_exchanges"],
            "staleness_max": sim["staleness_max"],
            "staleness_mean": round(sim["staleness_mean"], 3),
            "wire_mb": round(wire_mb, 3),
            "cum_wire_mb": round(cum_wire_mb, 3),
        }
        derived = f"stale_max={sim['staleness_max']:.0f}"
        if convergence:
            final, _, _ = train_mixture_gan(
                "DQGAN", steps=conv_steps,
                strategy_overrides={"schedule": "delayed",
                                    "staleness_tau": tau})
            f_row.update({"conv_steps": conv_steps, "conv_workers": 1,
                          "modes": final["modes"],
                          "hq_frac": final["hq_frac"], "fid": final["fid"]})
            derived += (f" modes={final['modes']}/8 hq={final['hq_frac']} "
                        f"fid={final['fid']}")
        frontier.append(f_row)
        row(f"sched/tau_frontier/tau={tau}", sim["mean_step_s"] * 1e6,
            derived)
    # wire accounting is monotone: staleness changes WHEN bytes move, not
    # how many — every τ point must report the same per-run bytes (this
    # catches n_exchanges drift in the server model), the cumulative
    # ledger must agree with the per-row sum, and more slack must not
    # slow the modeled clock.
    for a, b in zip(frontier, frontier[1:]):
        assert b["wire_mb"] == a["wire_mb"], (a, b)
        assert b["cum_wire_mb"] > a["cum_wire_mb"], (a, b)
        assert b["total_s"] <= a["total_s"] * (1 + 1e-9), \
            "more staleness slack must not slow the modeled clock"
    total_mb = sum(f_row["wire_mb"] for f_row in frontier)
    assert abs(frontier[-1]["cum_wire_mb"] - total_mb) < 0.01, \
        (frontier[-1]["cum_wire_mb"], total_mb)
    for f_row in frontier:
        assert f_row["staleness_max"] <= f_row["tau"], f_row

    out = {"d": d, "t_compute_us": t_compute * 1e6,
           "profile": profile.name, "local_k": K, "steps": steps,
           "link": {"bandwidth_Bps": S.LinkModel().bandwidth_Bps,
                    "latency_s": S.LinkModel().latency_s},
           "rows": rows,
           "tau_frontier": frontier,
           # deterministic PlanFamily wire model (no training) — gated by
           # --check-against alongside the schedule rows
           "comm_adaptive": comm_adaptive_model_rows(),
           # deterministic serving-engine model (benchmarks.serve_load) —
           # gated the same way
           "serve": _serve_model_rows()}
    if convergence:
        # real benchmark run (not the replayed-constants gate): attach the
        # measured split-phase overlap rows when `--only overlap` has
        # produced them — never gated (host wall clocks, not a model)
        measured = _load_overlap_measured()
        if measured:
            out["overlap_measured"] = measured
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    return out


# --------------------------------------------------------------------------- #
def _fsdp_strategies():
    from repro.strategy import (Compression, ExchangePlan, MomentCompression,
                                Strategy)

    def fsdp(zs, mom):
        return Strategy(
            compression=Compression(plan="uniform"),
            exchange=ExchangePlan(kind="two_phase", parallelism="fsdp",
                                  zero_stage=zs, worker_axes=("data",)),
            moments=MomentCompression(compressor=mom,
                                      error_feedback=mom != "identity"))

    repl = Strategy(compression=Compression(plan="uniform"),
                    exchange=ExchangePlan(kind="two_phase",
                                          worker_axes=("data",)))
    # the f32-moment variants isolate the all-gather leg's cost: same
    # memory frontier, 4 bytes/elem instead of ~1 on the return wire
    return (("replicated", repl),
            ("fsdp_zero2", fsdp(2, "qsgd8_linf")),
            ("fsdp_zero3", fsdp(3, "qsgd8_linf")),
            ("fsdp_zero2_f32mom", fsdp(2, "identity")),
            ("fsdp_zero3_f32mom", fsdp(3, "identity")))


def fsdp_model_rows(d, Ms):
    """Deterministic per-device memory + wire rows, keyed by
    strategy.short_hash() for the regression gate.

    Memory model (f32 Adam, message='grad'): the transient gradient
    buckets are 4d bytes on every path. Replicated DDP persists params
    + m + v (12d). fsdp shards the Adam moments and the all-gather EF
    residual down to 12d/W and (zero-3) adds the owner's parameter
    shard, 4d/W; the replicated parameter copy (4d) stays in the
    carried state on BOTH stages — the savings are the optimizer
    state, not the weights (DESIGN.md §15.6)."""
    rows = []
    for name, strat in _fsdp_strategies():
        for M in Ms:
            W = max(M, 1)
            if strat.exchange.fsdp:
                persistent = 4 * d + 12 * d / W + (
                    4 * d / W if strat.exchange.zero_stage == 3 else 0)
            else:
                persistent = 12 * d
            rows.append({
                "name": name, "M": M, "strategy": strat.short_hash(),
                "persistent_mb": round(persistent / 1e6, 4),
                "peak_mb": round((persistent + 4 * d) / 1e6, 4),
                "wire_mb": round(strat.modeled_wire_bytes(d, M) / 1e6, 4),
            })
    return rows


def bench_fsdp(quick: bool):
    """ZeRO memory/wire frontier on the dcgan32 parameter count
    (experiments/fsdp.json): modeled per-device peak bytes and
    per-round wire bytes for replicated two_phase vs compressed
    fsdp_zero2/zero3. The headline inequality — zero-3 peak strictly
    below replicated at M=8 — is asserted, not just reported."""
    from repro.models import gan

    cfg = gan.GANConfig().reduced() if quick else gan.GANConfig()
    params = gan.init(jax.random.key(0), cfg)
    d = sum(int(l.size) for l in jax.tree.leaves(params))
    Ms = (2, 4, 8) if quick else (2, 4, 8, 16, 32)
    rows = fsdp_model_rows(d, Ms)
    by = {(r["name"], r["M"]): r for r in rows}
    for r in rows:
        row(f"fsdp/{r['name']}/M={r['M']}", 0.0,
            f"peak={r['peak_mb']}MB wire={r['wire_mb']}MB")
    for M in Ms:
        repl, z2, z3 = (by[(n, M)] for n in
                        ("replicated", "fsdp_zero2", "fsdp_zero3"))
        # zero-3 ties replicated exactly at M=2 (4d + 12d/2 + 4d/2 = 12d)
        # and wins strictly from M=4 on
        assert z3["peak_mb"] <= repl["peak_mb"], (M, z3, repl)
        if M >= 4:
            assert z3["peak_mb"] < repl["peak_mb"], (M, z3, repl)
        assert z2["peak_mb"] <= z3["peak_mb"], (M, z2, z3)
        # quantizing the moments leg shrinks the wire, never the memory
        for name in ("fsdp_zero2", "fsdp_zero3"):
            q, f32 = by[(name, M)], by[(name + "_f32mom", M)]
            assert q["wire_mb"] < f32["wire_mb"], (M, q, f32)
            assert q["peak_mb"] == f32["peak_mb"], (M, q, f32)
    assert by[("fsdp_zero3", 8)]["peak_mb"] < by[("replicated", 8)]["peak_mb"]
    # sharding more workers only shrinks the per-device footprint
    for name in ("fsdp_zero2", "fsdp_zero3"):
        peaks = [by[(name, M)]["peak_mb"] for M in Ms]
        assert peaks == sorted(peaks, reverse=True), (name, peaks)
    out = {"quick": quick, "d": d, "Ms": list(Ms), "rows": rows}
    with open("experiments/fsdp.json", "w") as f:
        json.dump(out, f, indent=1)
    return out


def check_fsdp_regression(current: dict, baseline: dict,
                          tol: float = 0.10) -> list:
    """Gate experiments/fsdp.json rows against a committed baseline:
    rows matched by (strategy hash, M); >tol growth in modeled peak
    memory or wire bytes fails. Same stale-baseline refusal as the
    sched gate: zero hash matches means the schema/sweep moved."""
    fails = []
    base_rows = baseline.get("rows", [])
    cur_rows = current.get("rows", [])
    if base_rows and not all("strategy" in r for r in base_rows):
        return [
            "fsdp: baseline rows carry no strategy hash — regenerate "
            "with `python -m benchmarks.run --quick --only fsdp`"]
    base_by = {(r["strategy"], r["M"]): r for r in base_rows}
    matched = 0
    for r in cur_rows:
        b = base_by.get((r["strategy"], r["M"]))
        if b is None:
            continue
        matched += 1
        for f in ("peak_mb", "wire_mb"):
            if b.get(f) and r[f] > b[f] * (1 + tol):
                fails.append(
                    f"fsdp[{r['name']} M={r['M']} @{r['strategy']}] "
                    f"{f}: {r[f]:.6g} vs baseline {b[f]:.6g} "
                    f"(+{(r[f] / b[f] - 1) * 100:.1f}% > {tol * 100:.0f}%)")
    if base_rows and cur_rows and matched == 0:
        fails.append(
            "fsdp: no current row matches any baseline row by strategy "
            "hash — the sweep or strategy schema changed; regenerate "
            "the baseline")
    return fails


# --------------------------------------------------------------------------- #
def bench_compression(quick: bool):
    from repro.core import compressors as C

    n = 1 << (18 if quick else 22)
    key = jax.random.key(0)
    v = jax.random.normal(key, (n,))
    for name in ("qsgd8_linf", "qsgd8_l2", "qsgd8_l2_global",
                 "qsgd4_linf", "qsgd8_block256", "sign", "topk1"):
        comp = C.get(name)
        rt = jax.jit(lambda v, k, c=comp: c.roundtrip(v, k))
        us = _timeit(rt, v, key, iters=10)
        vhat = rt(v, key)
        err = float(jnp.sum((vhat - v) ** 2) / jnp.sum(v**2))
        ratio = 4 * n / comp.wire_bytes((n,))
        gbps = 4 * n / (us / 1e6) / 1e9
        row(f"compression/{name}", us,
            f"ratio={ratio:.1f}x delta_measured={1-err:.4f} gbps={gbps:.2f}")


# --------------------------------------------------------------------------- #
def bench_kernels(quick: bool):
    from repro.kernels import resolve_interpret
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.quantize import quantize_ef_blocked
    from repro.kernels.ref import flash_attention_ref, quantize_ef_ref

    # interpret-mode timings time the Pallas interpreter, not the kernel
    mode, note = (("interpret", "correctness-path; TPU perf is the target")
                  if resolve_interpret() else
                  ("compiled", f"device={jax.devices()[0].device_kind}"))
    R, Cc = (256, 512) if quick else (1024, 1024)
    key = jax.random.key(0)
    g = jax.random.normal(key, (R, Cc))
    e = jnp.zeros((R, Cc))
    r = jax.random.uniform(jax.random.fold_in(key, 1), (R, Cc))
    ref = jax.jit(quantize_ef_ref)
    us_ref = _timeit(ref, g, e, r, iters=10)
    bw = 4 * 3 * R * Cc / (us_ref / 1e6) / 1e9
    row("kernels/quantize_ef_ref(jnp)", us_ref, f"gbps={bw:.2f}")
    k_pallas = jax.jit(lambda g, e, r: quantize_ef_blocked(g, e, r))
    us_k = _timeit(k_pallas, g, e, r, iters=3, warmup=1)
    row(f"kernels/quantize_ef_pallas({mode})", us_k, note)

    S, D = (256, 64) if quick else (1024, 128)
    q = jax.random.normal(key, (4, S, D))
    kk = jax.random.normal(jax.random.fold_in(key, 2), (4, S, D))
    vv = jax.random.normal(jax.random.fold_in(key, 3), (4, S, D))
    refa = jax.jit(lambda q, k, v: flash_attention_ref(
        q[:, :, None], k[:, :, None], v[:, :, None])[:, :, 0])
    us_ra = _timeit(refa, q, kk, vv, iters=5)
    row("kernels/attention_ref(jnp)", us_ra,
        f"gflops={4*S*S*D*4/(us_ra/1e6)/1e9:.1f}")
    fa = jax.jit(lambda q, k, v: flash_attention(q, k, v))
    us_fa = _timeit(fa, q, kk, vv, iters=2, warmup=1)
    row(f"kernels/flash_attention_pallas({mode})", us_fa, note)


# --------------------------------------------------------------------------- #
def bench_roofline(quick: bool, dirpath: str = "experiments/dryrun"):
    """Roofline reporting as a first-class section: read the dry-run
    records (experiments/dryrun/*.json, produced by repro.launch.dryrun
    — the sweep itself needs a machine that can lower the production
    meshes), emit one row per record with the three roofline terms in
    seconds and the dominant bottleneck, and regenerate
    experiments/roofline.md. Rows ride the obs sink as bench_row events
    like every other section; absent records are a reported row, never a
    silent skip."""
    from benchmarks import roofline as R

    recs = R.load(dirpath) if os.path.isdir(dirpath) else []
    if not recs:
        row("roofline/none", 0.0,
            f"no dry-run records under {dirpath}/ — run `python -m "
            f"repro.launch.dryrun --all` on a host that can lower the "
            f"production meshes")
        return []
    for r in recs:
        name = f"roofline/{r['arch']}/{r['shape']}/{r['mesh']}"
        if r["status"] != "ok":
            row(name, 0.0,
                f"status={r['status']} "
                f"{(r.get('reason') or r.get('error') or '')[:60]}")
            continue
        rf = r["roofline"]
        total = rf["compute_s"] + rf["memory_s"] + rf["collective_s"]
        row(name, total * 1e6,
            f"bottleneck={r['bottleneck']} "
            f"compute_s={rf['compute_s']:.3e} "
            f"memory_s={rf['memory_s']:.3e} "
            f"collective_s={rf['collective_s']:.3e}")
    R.main(["--dir", dirpath,
            "--out", os.path.join(os.path.dirname(dirpath), "roofline.md")])
    return recs


# --------------------------------------------------------------------------- #
def bench_comm(quick: bool, sim_steps: int = 0):
    """repro.comm telemetry on the two smoke configs: per-step + cumulative
    wire bytes, achieved compression ratio, and how many tensors the seed
    per-tensor two_phase planner bounces to `sim` vs the bucketed planner.
    Two worker counts: 8 (power-of-two pod) and 12 (3 hosts x 4 chips —
    the non-power-of-two case where per-tensor chunking falls apart)."""
    import repro.configs as cfgs
    from repro import comm
    from repro.models import build
    from repro.strategy import Strategy

    # one Strategy object defines both modes' wire: the seed mode drops
    # its comm plan (per-tensor exchange), the bucketed mode keeps it
    strat = Strategy.from_legacy(exchange="two_phase",
                                 compressor="qsgd8_linf",
                                 comm_plan="uniform", bucket_mb=1.0)
    kind, comp = strat.exchange.kind, strat.compression.compressor
    sim_steps = sim_steps or (10 if quick else 100)
    out = {"sim_steps": sim_steps, "strategy": strat.to_json(),
           "configs": {}}
    for arch in ("dcgan32", "gemma-2b"):
        cfg = cfgs.get(arch).reduced()
        bundle = build(cfg)
        params = jax.eval_shape(lambda k: bundle.init(k, max_seq=32),
                                jax.random.key(0))
        shapes = jax.tree.map(lambda x: tuple(x.shape), params)
        rec = {}
        for W in (8, 12):
            for mode in ("seed", "bucketed"):
                if mode == "seed":
                    led = comm.CommLedger.from_tree(
                        kind, comp, shapes, None, W)
                else:
                    layout, plan = strat.compression.build(shapes, None, W)
                    led = comm.CommLedger.from_plan(
                        layout, plan, kind, W, comp)
                led.tick(sim_steps)
                s = led.summary()
                rec[f"{mode}_W{W}"] = s
                row(f"comm/{arch}/W{W}/{mode}", 0.0,
                    f"wire_mb_step={s['wire_bytes_per_step']/1e6:.3f} "
                    f"cum_wire_mb={s['cumulative_wire_bytes']/1e6:.1f} "
                    f"ratio={s['compression_ratio']} "
                    f"fallbacks={s['n_fallbacks']}/{s['n_entries']}")
                # the bucketed planner's per-bucket wire accounting
                # (bits, payload, analytic δ) rides along as CSV rows
                for pb in s.get("per_bucket", []):
                    row(f"comm/{arch}/W{W}/{mode}/bucket{pb['bucket']}",
                        0.0,
                        f"comp={pb['compressor']} bits={pb['bits']} "
                        f"elems={pb['elems']} "
                        f"payload_b={pb['payload_bytes']} "
                        f"delta={pb['delta']}")
                if _SINK is not None:
                    _SINK.emit("comm_summary", arch=arch, workers=W,
                               mode=mode, **s)
            assert (rec[f"bucketed_W{W}"]["n_fallbacks"]
                    <= rec[f"seed_W{W}"]["n_fallbacks"])
        # the non-power-of-two worker count is where bucketing pays off
        assert (rec["bucketed_W12"]["n_fallbacks"]
                < rec["seed_W12"]["n_fallbacks"])
        out["configs"][arch] = rec
    with open("experiments/comm.json", "w") as f:
        json.dump(out, f, indent=1)
    return out


# --------------------------------------------------------------------------- #
# round-adaptive compression (repro.comm PlanFamily, DESIGN.md §10)
# --------------------------------------------------------------------------- #
# The mixture-GAN sizing for the adaptive frontier: a bucket cap small
# enough to give the descent real per-bucket structure, and a budget that
# bites at full participation (the ~41 KB 8-bit payload must not fit) so
# the family actually fans out across participation counts.
MIX_ADAPTIVE = {"bucket_mb": 0.0625, "comm_budget_mb": 0.024}
ADAPTIVE_PARTICIPATIONS = (1.0, 0.5, 0.25)
ADAPTIVE_M = 8


def _mix_adaptive_strategy(participation: float, adaptive: bool):
    """One frontier cell: the adaptive_budget/byte_budget pair resized
    for the 2-D mixture GAN, at a given participation."""
    from repro.strategy import get_preset

    return get_preset("adaptive_budget").evolve(
        participation=participation, comm_adaptive=adaptive,
        worker_axes=("data",), **MIX_ADAPTIVE)


def _mix_adaptive_ledger(strat, M):
    """(CommLedger, plan_for_n) for one frontier strategy over the
    mixture-GAN shapes — pure planner arithmetic, no devices."""
    from repro import comm
    from repro.models.gan import GANConfig, mlp_gan_init

    cfg = GANConfig(name="mix", image_size=0, data_dim=2, latent_dim=16,
                    hidden=128)
    params = jax.eval_shape(lambda k: mlp_gan_init(k, cfg),
                            jax.random.key(0))
    shapes = jax.tree.map(lambda x: tuple(x.shape), params)
    comp = strat.compression
    if comp.adaptive:
        layout, family = comp.build_family(shapes, None, M)
        plan = family.full
    else:
        layout, plan = comp.build(shapes, None, M)
        family = None
    led = comm.CommLedger.from_plan(
        layout, plan, strat.exchange.kind, M, comp.compressor,
        family=family)
    return led, (family.plan_for if family is not None
                 else lambda n: plan)


def comm_adaptive_model_rows():
    """Deterministic PlanFamily wire model on the mixture-GAN shapes —
    the rows the benchmark-regression gate checks (no devices, no
    training: pure planner arithmetic, keyed by strategy.short_hash())."""
    from repro.sched import n_participants

    M = ADAPTIVE_M
    rows = []
    for p in ADAPTIVE_PARTICIPATIONS:
        for adaptive in (False, True):
            strat = _mix_adaptive_strategy(p, adaptive)
            led, plan_for = _mix_adaptive_ledger(strat, M)
            n = n_participants(p, M)
            rows.append({
                "strategy": strat.short_hash(),
                "mode": "adaptive" if adaptive else "static",
                "participation": p,
                "participants": n,
                "wire_mb": round(led.round_bytes(n)[0] / 1e6, 4),
                "payload_bytes": plan_for(n).payload_bytes,
            })
    return rows


def bench_comm_adaptive(quick: bool):
    """Measured bytes-vs-convergence frontier for round-adaptive
    compression: the mixture GAN trained over M=8 workers at
    participation ∈ {1.0, 0.5, 0.25}, static `byte_budget` descent vs
    the `adaptive_budget` PlanFamily (experiments/comm_adaptive.json).

    The acceptance inequalities are asserted, not just reported:
      * full participation: adaptive ≡ static (identical metrics — the
        single-selected-member family is bit-exact with the static plan);
      * the equal-bytes comparison: adaptive at participation 0.5 moves
        no more cumulative wire bytes than static at full participation
        and matches or beats its convergence metric;
      * at the same participation 0.5, adaptive (which re-spends the
        absent workers' budget on finer bits) is no worse than static.
    """
    from jax.sharding import AxisType

    from benchmarks.gan_common import train_mixture_gan

    from repro.sched import n_participants

    if jax.device_count() < 4:
        # the frontier needs real workers
        _rerun_on_host_devices("comm_adaptive", quick)
        return None

    M = ADAPTIVE_M if jax.device_count() >= ADAPTIVE_M else jax.device_count()
    mesh = jax.make_mesh((M,), ("data",), axis_types=(AxisType.Auto,))
    steps = 400 if quick else 1500
    rows = []
    for p in ADAPTIVE_PARTICIPATIONS:
        for adaptive in (False, True):
            strat = _mix_adaptive_strategy(p, adaptive)
            overrides = dict(participation=p,
                             comm_adaptive=adaptive, **MIX_ADAPTIVE,
                             comm_plan="delta_budget",
                             exchange=strat.exchange.kind)
            final, _, st = train_mixture_gan(
                "DQGAN", steps=steps, strategy_overrides=overrides,
                mesh=mesh)
            # bill the run's bytes with the participation-aware ledger
            # (pure planner arithmetic on the same strategy — no second
            # trainer build)
            led, _ = _mix_adaptive_ledger(strat, M)
            n = n_participants(p, M)
            led.tick(steps, participants=n)
            r = {"mode": "adaptive" if adaptive else "static",
                 "participation": p, "participants": n, "steps": steps,
                 "strategy": strat.short_hash(),
                 "wire_mb_round": round(led.round_bytes(n)[0] / 1e6, 4),
                 "cum_wire_mb": round(led.cumulative_wire_bytes / 1e6, 2),
                 "modes": final["modes"], "hq_frac": final["hq_frac"],
                 "fid": final["fid"]}
            rows.append(r)
            row(f"comm_adaptive/{r['mode']}/p={p}", 0.0,
                f"cum_wire_mb={r['cum_wire_mb']} modes={r['modes']}/8 "
                f"hq={r['hq_frac']} fid={r['fid']}")

    by = {(r["mode"], r["participation"]): r for r in rows}
    # Hard assertions: same-process determinism and byte accounting only.
    # Full participation: the single-selected-member family is bit-exact
    # with the static plan, so BOTH runs of this very process must agree
    # on every field.
    for fld in ("modes", "hq_frac", "fid", "cum_wire_mb"):
        assert by[("adaptive", 1.0)][fld] == by[("static", 1.0)][fld], \
            (fld, by[("adaptive", 1.0)], by[("static", 1.0)])
    # byte-budget invariant (structural, deterministic): every round's
    # fleet-average bytes fit B times the two_phase collective multiplier
    # — each member's payload <= its effective budget B*M/n by
    # construction, so (n/M)*multiplier*payload <= multiplier*B
    ad, st_full = by[("adaptive", 0.5)], by[("static", 1.0)]
    st_half = by[("static", 0.5)]
    bound_mb = (MIX_ADAPTIVE["comm_budget_mb"] * 2 * (M - 1) / M
                * steps * (1 << 20) / 1e6)
    for r in rows:
        assert r["cum_wire_mb"] <= bound_mb * 1.01, (r, bound_mb)
    # Convergence comparisons are NOT hard-gated (mixture-GAN metrics are
    # jax-version sensitive — same policy as check_sched_regression);
    # record the outcomes in the artifact and warn loudly on a miss.
    acceptance = {
        # the equal-bytes frontier point: adaptive@0.5's cumulative bytes
        # track static@1.0's (both are prefix cuts near B per round —
        # exact today, granularity-dependent after a resize)
        "equal_bytes_ok": bool(
            ad["cum_wire_mb"] <= st_full["cum_wire_mb"] * 1.02),
        # adaptive@0.5 matches-or-beats static@1.0 at equal bytes
        "equal_bytes_fid_ok": bool(ad["fid"] <= st_full["fid"] * 1.10),
        "equal_bytes_modes_ok": bool(ad["modes"] >= st_full["modes"] - 1),
        # same participation: the re-invested budget must not hurt
        "same_participation_fid_ok": bool(ad["fid"] <= st_half["fid"] * 1.10),
    }
    for name, ok in acceptance.items():
        if not ok:
            print(f"WARNING: comm_adaptive acceptance check {name} "
                  f"failed: adaptive@0.5={ad} static@1.0={st_full} "
                  f"static@0.5={st_half}", flush=True)

    out = {"M": M, "steps": steps, "sizing": MIX_ADAPTIVE,
           "acceptance": acceptance,
           "model_rows": comm_adaptive_model_rows(), "rows": rows}
    with open("experiments/comm_adaptive.json", "w") as f:
        json.dump(out, f, indent=1)
    return out


# --------------------------------------------------------------------------- #
# continuous-batching serving (repro.serve)
# --------------------------------------------------------------------------- #
def _serve_model_rows():
    """Lazy import shim so bench_sched can embed the serve model rows
    without paying the repro.serve import on non-serve sections."""
    from benchmarks.serve_load import serve_model_rows
    return serve_model_rows()


def bench_serve(quick: bool):
    """Measured serving benchmark on the reduced gemma-2b:

    1. closed loop — the continuous-batching engine vs the sequential
       batch-1 baseline on the same warm request set; the engine's
       tokens/s must strictly win (the whole point of batching decode),
       and its decode step must have compiled exactly once across all
       request churn.
    2. open loop — seeded Poisson arrivals swept over offered QPS on a
       virtual clock (measured step walls, exact arrival times):
       latency p50/p99, tokens/s, KV-block occupancy per QPS.

    Writes experiments/serve.json with the measured rows plus the
    deterministic `serve_model_rows()` the --check-against gate compares
    (the same rows bench_sched embeds under "serve")."""
    import repro.configs as cfgs
    from benchmarks.serve_load import (gen_requests, run_open_loop,
                                       serve_model_rows)

    from repro.models import model as lm
    from repro.serve import Engine, Request, SequentialGenerator, ServeConfig

    cfg = cfgs.get("gemma-2b").reduced()
    params = lm.init(jax.random.key(0), cfg, 0)
    scfg = ServeConfig(max_batch=4 if quick else 8, block_size=8,
                       num_blocks=64 if quick else 128,
                       max_blocks_per_seq=8,
                       prompt_buckets=(8, 16, 32))
    n_req, gen = (8, 6) if quick else (24, 12)
    max_prompt = 24

    import numpy as np
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(1, cfg.vocab_size,
                                 int(rng.integers(3, max_prompt))))
               for _ in range(n_req)]

    # -- closed loop: engine vs sequential on identical requests ----------- #
    eng = Engine(cfg, scfg, params)
    warm = [Request(rid=10_000 + i, prompt=list(p), max_new=gen)
            for i, p in enumerate(prompts)]
    eng.run(warm)                                     # compile + correctness
    reqs = [Request(rid=i, prompt=list(p), max_new=gen)
            for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    out = eng.run(reqs)
    dt_eng = time.perf_counter() - t0
    toks = sum(len(out[r.rid]) for r in reqs)
    tps_eng = toks / dt_eng

    seq = SequentialGenerator(cfg, scfg, params)
    seq.generate(list(prompts[0]), gen, rid=20_000)   # compile
    t0 = time.perf_counter()
    seq_out = {i: seq.generate(list(p), gen, rid=i)
               for i, p in enumerate(prompts)}
    dt_seq = time.perf_counter() - t0
    tps_seq = sum(len(v) for v in seq_out.values()) / dt_seq

    assert seq_out == {r.rid: out[r.rid] for r in reqs}, \
        "engine and sequential baseline disagree on greedy tokens"
    assert len(eng.decode_traces) == 1, \
        f"decode step retraced: {len(eng.decode_traces)} compiles"
    assert tps_eng > tps_seq, \
        (f"continuous batching must beat sequential decode at batch "
         f">= 4: engine {tps_eng:.1f} tok/s vs sequential {tps_seq:.1f}")
    row("serve/closed_loop/engine", dt_eng / max(eng.scfg.max_batch, 1) * 1e6,
        f"tokens_per_s={tps_eng:.1f} batch={scfg.max_batch} "
        f"traces={len(eng.decode_traces)}")
    row("serve/closed_loop/sequential", dt_seq / n_req * 1e6,
        f"tokens_per_s={tps_seq:.1f}")

    # -- open loop: offered-QPS sweep on the warm engine -------------------- #
    sweep = []
    for j, qps in enumerate((4.0, 16.0) if quick else (2.0, 8.0, 32.0)):
        load = gen_requests(n_req, qps, seed=j + 1,
                            vocab=cfg.vocab_size, max_prompt=max_prompt,
                            max_new=gen)
        r = run_open_loop(eng, load, rid_base=1000 * (j + 1))
        r["qps"] = qps
        sweep.append(r)
        row(f"serve/qps={qps}", r["mean_step_s"] * 1e6,
            f"p50={r['latency_p50_s']}s p99={r['latency_p99_s']}s "
            f"tok/s={r['tokens_per_s']} "
            f"kv_peak={r['kv_occupancy_peak']}")
    assert len(eng.decode_traces) == 1, \
        f"decode step retraced during QPS sweep: {len(eng.decode_traces)}"

    out_doc = {
        "arch": cfg.name,
        "serve_config": {"max_batch": scfg.max_batch,
                         "block_size": scfg.block_size,
                         "num_blocks": scfg.num_blocks,
                         "max_blocks_per_seq": scfg.max_blocks_per_seq,
                         "prompt_buckets": list(scfg.prompt_buckets)},
        "closed_loop": {"tokens_per_s_engine": round(tps_eng, 1),
                        "tokens_per_s_sequential": round(tps_seq, 1),
                        "speedup": round(tps_eng / tps_seq, 2),
                        "decode_traces": len(eng.decode_traces)},
        "qps_sweep": sweep,
        "model": serve_model_rows(),
    }
    with open("experiments/serve.json", "w") as f:
        json.dump(out_doc, f, indent=1)
    return out_doc


# --------------------------------------------------------------------------- #
# benchmark-regression gate (CI)
# --------------------------------------------------------------------------- #
_GATED_FIELDS = ("mean_step_s", "wire_mb")   # wall-clock model + wire bytes


def check_sched_regression(current: dict, baseline: dict,
                           tol: float = 0.10) -> list:
    """Compare a bench_sched result dict against a committed baseline.
    Returns a list of human-readable failures: any row present in both
    whose modeled seconds/step or wire bytes grew by more than `tol`
    (improvements and new rows pass; convergence metrics are not gated —
    they are host-independent but jax-version sensitive).

    Rows are matched by the STRUCTURAL identity of their strategy — the
    `strategy.short_hash()` recorded per row — not by schedule/compressor
    label, so a sweep whose "local_k" silently changed meaning (different
    K, different exchange, ...) is a new row, never a bogus comparison;
    a baseline predating the hashes is refused outright."""
    fails = []

    def gate(cur_rows, base_rows, key_fields, human_fields, label):
        if base_rows and not all("strategy" in r for r in base_rows):
            fails.append(
                f"{label}: baseline rows carry no strategy hash "
                f"(pre-strategy format) — regenerate the baseline with "
                f"`python -m benchmarks.run --quick --only sched`")
            return
        base_by_key = {tuple(r[k] for k in key_fields): r for r in base_rows}
        matched = 0
        for r in cur_rows:
            b = base_by_key.get(tuple(r[k] for k in key_fields))
            if b is None:
                continue
            matched += 1
            for f in _GATED_FIELDS:
                if f not in r or not b.get(f):
                    continue
                if r[f] > b[f] * (1 + tol):
                    who = ", ".join(f"{k}={r[k]}" for k in human_fields)
                    fails.append(
                        f"{label}[{who} @{r['strategy']}] "
                        f"{f}: {r[f]:.6g} vs baseline {b[f]:.6g} "
                        f"(+{(r[f] / b[f] - 1) * 100:.1f}% > {tol * 100:.0f}%)")
        if base_rows and cur_rows and matched == 0:
            # a sweep/schema change shifted EVERY hash: that is a stale
            # baseline, not a clean bill of health
            fails.append(
                f"{label}: no current row matches any baseline row by "
                f"strategy hash — the sweep or strategy schema changed; "
                f"regenerate the baseline")

    gate(current.get("rows", []), baseline.get("rows", []),
         ("strategy", "M"), ("schedule", "compressor", "M"), "sched")
    gate(current.get("tau_frontier", []), baseline.get("tau_frontier", []),
         ("strategy",), ("tau",), "tau_frontier")
    gate(current.get("comm_adaptive", []),
         baseline.get("comm_adaptive", []),
         ("strategy",), ("mode", "participation"), "comm_adaptive")
    gate(current.get("serve", []), baseline.get("serve", []),
         ("strategy",), ("qps",), "serve")
    return fails


# --------------------------------------------------------------------------- #
def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small sizes/steps (CI mode)")
    ap.add_argument("--only", default="",
                    help="comma list: convergence,speedup,compression,"
                         "kernels,comm,comm_adaptive,overlap,sched,"
                         "serve,roofline,fsdp")
    ap.add_argument("--check-against", default="",
                    help="baseline JSON to gate against: the sched section "
                         "(committed experiments/baselines/sched_quick.json) "
                         "or the fsdp section (fsdp_quick.json) — >10% "
                         "regression in the modeled numbers fails the run")
    ap.add_argument("--obs-sink", default="", metavar="PATH",
                    help="also write every row as a repro.obs bench_row "
                         "event (JSONL) for `python -m repro.obs report`")
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None
    if args.check_against and (only is None or not only & {"sched", "fsdp"}):
        ap.error("--check-against gates the sched or fsdp section; "
                 "add --only sched or --only fsdp")
    if args.check_against and only and {"sched", "fsdp"} <= only:
        ap.error("--check-against takes one baseline file; gate sched and "
                 "fsdp in separate runs")
    global _SINK
    if args.obs_sink:
        from repro import obs as obs_api
        _SINK = obs_api.make_sink(args.obs_sink)
    print("name,us_per_call,derived")
    os.makedirs("experiments", exist_ok=True)
    if not only or "compression" in only:
        bench_compression(args.quick)
    if not only or "comm" in only:
        bench_comm(args.quick)
    if only and "comm_adaptive" in only:
        # opt-in: trains the mixture GAN over 8 (forced) host devices —
        # not part of the default single-device sweep
        bench_comm_adaptive(args.quick)
    if only and "overlap" in only:
        # opt-in for the same reason: times the jitted step over 8
        # (forced) host devices, overlap on vs off; run it BEFORE a full
        # sched/speedup regen so those artifacts embed the measured rows
        bench_overlap(args.quick)
    if not only or "kernels" in only:
        bench_kernels(args.quick)
    if not only or "sched" in only:
        model_inputs = None
        baseline = None
        if args.check_against:
            with open(args.check_against) as f:
                baseline = json.load(f)
            # replay the model on the baseline's machine constants so the
            # comparison is model-vs-model, not runner-vs-runner
            model_inputs = (baseline["t_compute_us"] / 1e6, baseline["d"])
            print(f"# sched: gating against {args.check_against} "
                  f"(t_compute={baseline['t_compute_us']:.0f}us "
                  f"d={baseline['d']})", flush=True)
            if SCHED_MODEL_STEPS[args.quick] != baseline.get("steps"):
                print(f"ERROR: tier mismatch — this run would simulate "
                      f"steps={SCHED_MODEL_STEPS[args.quick]} but the "
                      f"baseline was generated with "
                      f"steps={baseline.get('steps')}; run the gate with "
                      f"the baseline's tier (--quick for sched_quick.json)"
                      f", or regenerate the baseline", flush=True)
                sys.exit(2)
        current = bench_sched(
            args.quick, model_inputs=model_inputs,
            convergence=baseline is None,
            # keep the gate's replayed-constants output apart from a real
            # benchmark result (it would otherwise clobber a full-tier
            # experiments/sched.json generated on this machine)
            out_path=("experiments/sched_gate.json" if baseline is not None
                      else "experiments/sched.json"))
        if baseline is not None:
            fails = check_sched_regression(current, baseline)
            for f_msg in fails:
                print(f"REGRESSION: {f_msg}", flush=True)
            if fails:
                sys.exit(1)
            print("# sched: regression gate passed", flush=True)
    if only and "fsdp" in only:
        current = bench_fsdp(args.quick)
        if args.check_against:
            with open(args.check_against) as f:
                baseline = json.load(f)
            fails = check_fsdp_regression(current, baseline)
            for f_msg in fails:
                print(f"REGRESSION: {f_msg}", flush=True)
            if fails:
                sys.exit(1)
            print("# fsdp: regression gate passed", flush=True)
    if not only or "serve" in only:
        bench_serve(args.quick)
    if not only or "roofline" in only:
        bench_roofline(args.quick)
    if not only or "speedup" in only:
        bench_speedup(args.quick)
    if not only or "convergence" in only:
        bench_convergence(args.quick)
    if _SINK is not None:
        _SINK.close()
        _SINK = None


if __name__ == "__main__":
    main()
