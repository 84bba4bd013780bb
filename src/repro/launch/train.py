"""Training launcher: end-to-end DQGAN training of any registered arch on
the local device set (CPU smoke / real TPU alike).

    PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --smoke \
        --steps 100 --compressor qsgd8_linf --exchange sim

Distribution strategy (repro.strategy, DESIGN.md §9): the strategy flags
below are auto-generated from the typed component schemas; start from a
preset or a serialized strategy and override per flag:

    # the paper's setting by name:
    ... --preset paper_dqgan

    # a preset with one axis overridden:
    ... --preset ssp_server --staleness-tau 2

    # an exact strategy from a checkpoint / experiments JSON:
    ... --strategy-json '{"schedule": {"kind": "delayed", "tau": 4}}'

Communication planning (repro.comm, DESIGN.md §3): pass ``--comm-plan`` to
bucket the gradient pytree into flat worker-divisible buckets and assign a
compressor per bucket; each log line then carries the wire-telemetry
fields ``wire_mb_step`` / ``cum_wire_mb`` / ``comm_ratio``:

    # DDP-style bucketing, one compressor everywhere (paper semantics):
    ... --comm-plan uniform --exchange two_phase --compressor qsgd8_linf

    # keep small buckets (biases/norms) full precision:
    ... --comm-plan size_tiered --bucket-mb 4

    # fit a byte budget by per-bucket bit-width descent:
    ... --comm-plan delta_budget --comm-budget-mb 2.5

    # round-adaptive PlanFamily: when only n of M workers report, the
    # absent workers' budget buys the participants finer bits
    # (DESIGN.md §10; log rows gain ``participants``):
    ... --preset adaptive_budget --participation 0.5

Execution schedule (repro.sched, DESIGN.md §5, §8): ``--schedule`` picks
when workers exchange; log rows then carry ``round`` and the simulated
wall clock (``sim_clock_s``) from the straggler-aware cost model:

    # exchange every 4 steps, message accumulates between rounds:
    ... --schedule local_k --local-k 4

    # one-step-stale exchange overlapping compute, heterogeneous workers:
    ... --schedule delayed --straggler-profile mild

    # bounded staleness τ=4: the parameter-server push/pull pipeline —
    # log rows gain per-step max/mean staleness from the version vector:
    ... --schedule delayed --staleness-tau 4

    # each round only half the workers report; the rest accumulate EF:
    ... --participation 0.5

Observability (repro.obs, DESIGN.md §11): ``--obs-metrics wire|full``
turns on on-device telemetry (empirical δ, EF residual norms, per-bucket
gradient moments, staleness histograms) with a bit-exactness guarantee —
the trajectory is identical to ``--obs-metrics off``. ``--obs-sink
PATH`` writes the versioned JSONL event stream (run meta, log rows,
synced step/interval timings, obs metrics, comm summaries) for
``python -m repro.obs report PATH``; the default sink renders log rows
on stdout exactly as before. ``--obs-spans`` adds named profiler spans
(lookahead/field/exchange/pack/compress/apply on device; one
StepTraceAnnotation per loop iteration, and data/dispatch/sync/eval
inside it, on the host):

    ... --preset adaptive_budget --obs-metrics full --obs-sink run.jsonl

Checkpointing: ``--checkpoint PATH`` saves the FULL ``DQState`` (params,
optimizer moments, prev_grad, EF residuals incl. comm-plan bucket
entries, schedule buffers, a stateful field's per-worker state such as
biggan128's spectral-norm vectors) at the end and every
``--checkpoint-every N`` steps; ``--resume PATH`` restores it and
continues from the saved step.

For the paper's own experiment (DCGAN), use examples/train_gan_images.py
which adds the WGAN weight clipping + evaluation metrics.
"""
from __future__ import annotations

import argparse
import time
import zipfile
from contextlib import nullcontext
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

import repro.configs as cfgs
from repro import checkpoint
from repro import obs as obs_api
from repro import strategy as strategy_api
from repro.configs.base import DQConfig
from repro.core.dqgan import DQGAN
from repro.data import lm_batch_iterator
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build
from repro.parallel import sharding as shd
from repro.sched import clock as sclock
from repro.sched import straggler as sstrag


class TrainRun(NamedTuple):
    """What `run` leaves behind: the log rows, the jitted step, and the
    last state/batch/key it was called with (enough to lower the step
    again, e.g. to read its compiled HLO under `mesh`)."""
    history: list
    step: Any
    state: Any
    batch: Any
    key: Any
    mesh: Any


def main(argv=None):
    """CLI entry point; returns the log rows."""
    return run(argv).history


def run(argv=None) -> TrainRun:
    """Parse `argv` as the CLI does, train, and return a `TrainRun`."""
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--optimizer", default="oadam")
    # the distribution-strategy surface is generated from the
    # repro.strategy component schemas (one definition for the dataclass,
    # the JSON schema and these flags) — includes --preset/--strategy-json
    # and the legacy spellings (--compressor, --schedule, ...).
    strategy_api.add_strategy_args(ap)
    ap.add_argument("--checkpoint", default="",
                    help="save the full DQState here (end of run + "
                         "--checkpoint-every). A path ending in .npz "
                         "uses the single-archive format; anything else "
                         "is a per-host sharded directory (manifest + "
                         "one shard file per host, DESIGN.md §15.5)")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="also save every N steps (0 = only at the end)")
    ap.add_argument("--checkpoint-shards", type=int, default=0,
                    help="shard-file count for the sharded checkpoint "
                         "format (0 = one per host)")
    ap.add_argument("--resume", default="",
                    help="restore a full DQState checkpoint (either "
                         "format; sharded checkpoints reshard onto this "
                         "run's device count) and continue")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--obs-sink", default="", metavar="PATH",
                    help="run-sink backend: '' (quiet stdout, the "
                         "default rendering), 'stdout' (verbose), "
                         "'null', or a JSONL file path for "
                         "`python -m repro.obs report`")
    ap.add_argument("--profile-steps", type=int, default=0, metavar="N",
                    help="profile a window of N steps and emit one "
                         "schema-v2 `profile` event into the sink "
                         "(repro.obs.profile; implies profiling on — "
                         "--obs-profile alone uses the default window)")
    ap.add_argument("--profile-trace-dir", default="", metavar="DIR",
                    help="also capture a jax.profiler trace of the "
                         "profiled window into DIR (TensorBoard)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = cfgs.get(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    bundle = build(cfg)

    n_dev = jax.device_count()
    mesh = None
    worker_axes = ()
    pspecs = None
    bspec = None
    if n_dev > 1:
        worker_axes = ("data",)

    try:
        strat = strategy_api.strategy_from_args(args,
                                                worker_axes=worker_axes)
    except strategy_api.StrategyError as e:
        ap.error(str(e))
    sched = strat.schedule.runtime()

    key = jax.random.key(args.seed)
    params = bundle.init(key, max_seq=args.seq)
    if n_dev > 1:
        from jax.sharding import AxisType, PartitionSpec as P

        # A 'model' axis only where the sharding rules split some leaf
        # over it, 2 wide when the device count allows. fsdp shards
        # optimizer state over the data axis and needs every leaf in a
        # flat bucket, so it keeps that axis at size 1 (DESIGN.md §15.1).
        # The GAN configs have no such leaf: a pure data mesh, every
        # device a paper-worker, and a shard_map manual over every mesh
        # axis — which the fused Pallas kernel needs, since the TPU
        # compiler cannot partition a Mosaic kernel over an auto axis.
        if shd.uses_axis(shd.param_specs(params, cfg, "dp"), "model"):
            model_n = (2 if n_dev % 2 == 0 and n_dev > 2
                       and not strat.exchange.fsdp else 1)
            mesh = jax.make_mesh((n_dev // model_n, model_n),
                                 ("data", "model"),
                                 axis_types=(AxisType.Auto,) * 2)
        else:
            mesh = jax.make_mesh((n_dev,), ("data",),
                                 axis_types=(AxisType.Auto,))
        bspec = P(("data",))

    dq = DQConfig.from_strategy(
        strat, optimizer=args.optimizer, lr=args.lr,
        message="update" if args.optimizer == "omd" else "grad",
    )
    if mesh is not None:
        pspecs = shd.param_specs(params, cfg, "dp", mesh)
        shards = shd.shardings(pspecs, mesh)
        params = jax.tree.map(jax.device_put, params, shards)

    trainer = DQGAN(field_fn=bundle.field_fn, dq=dq, mesh=mesh,
                    param_specs=pspecs, batch_spec=bspec)

    def state_shardings():
        if mesh is None:
            return None
        from jax.sharding import NamedSharding
        return jax.tree.map(lambda s: NamedSharding(mesh, s),
                            trainer.state_specs(params))

    def save_ckpt(path, st, step):
        meta = {"strategy": strat.to_json()}
        if path.endswith(".npz"):
            checkpoint.save(path, st, step=step, meta=meta)
        else:
            checkpoint.save_sharded(path, st, step=step, meta=meta,
                                    mesh=mesh,
                                    n_shards=args.checkpoint_shards or None)

    start = 0
    state = trainer.init(params)
    if args.resume:
        try:
            checkpoint.verify_strategy(args.resume, strat)
            if checkpoint.is_sharded(args.resume):
                saved_mesh = checkpoint.read_manifest(
                    args.resume).get("mesh")
                cur = (None if mesh is None else
                       {"axis_names": [str(a) for a in mesh.axis_names],
                        "shape": [int(mesh.shape[a])
                                  for a in mesh.axis_names]})
                if saved_mesh != cur:
                    print(f"# resume: resharding {saved_mesh} -> {cur}",
                          flush=True)
                state = checkpoint.restore_sharded(args.resume, state,
                                                   state_shardings())
            else:
                state = checkpoint.restore(args.resume, state,
                                           state_shardings())
        except (ValueError, OSError, zipfile.BadZipFile) as e:
            # strategy/shape mismatch, missing file, or corrupt archive —
            # all refuse cleanly instead of a restore-time traceback
            raise SystemExit(f"--resume refused:\n{e}") from None
        start = int(jax.device_get(state.step))
        print(f"# resumed from {args.resume} at step {start}", flush=True)
    step = jax.jit(trainer.step, static_argnums=(3,), donate_argnums=(0,))

    ledger = trainer.comm_ledger(params)
    sk_n, sk_bytes = ledger.skipped_leaves()
    if sk_n:
        # sharded leaves that bypassed the flat-bucket pipeline ride the
        # (slower, per-tensor) path — surface it once, loudly
        print(f"# comm: WARNING {sk_n} sharded leaf(s) bypass bucketing "
              f"({sk_bytes / 1e6:.2f} MB/step on the per-tensor path)",
              flush=True)
    if strat.compression.bucketing:
        layout, cplan = trainer._comm(params)
        print(f"# comm: {layout.describe()}", flush=True)
        print(f"# comm: {cplan.describe()}", flush=True)
        family = trainer._family(params)
        if family is not None:
            print(f"# comm: {family.describe()}", flush=True)
    # count-exact participation: the per-round participant count is a
    # static function of (fraction, W) — the ledger bills each round at
    # the bytes the reporting workers actually move (selected-plan
    # payload under an adaptive family, DESIGN.md §10.3)
    from repro.sched import n_participants
    n_part = (n_participants(strat.participation.fraction,
                             trainer.n_workers)
              if trainer.n_workers > 1 and strat.participation.partial
              else None)
    profile = strat.participation.profile()
    link = sclock.LinkModel()
    W = max(trainer.n_workers, 1)
    # price the modeled exchange at what a round actually moves — under
    # partial participation that is the selected family member's payload
    # (round_bytes), not the full-M plan
    t_ex = (link.exchange_time(ledger.round_bytes(n_part)[0])
            if W > 1 else 0.0)
    print(f"# strategy: {strat.describe()} [{strat.short_hash()}]",
          flush=True)

    # structured run sink (repro.obs): every log/timing/telemetry row is
    # one schema event keyed by the strategy's structural identity; the
    # default backend renders log rows on stdout exactly as before
    sink = obs_api.make_sink(args.obs_sink, strategy_hash=strat.short_hash(),
                             tee_stdout=True)
    obs_spans = strat.observability.spans
    # host-side step profiler (repro.obs.profile, DESIGN.md §12.1) — a
    # NullStepProfiler when off, so the hot loop carries no conditionals
    # and the compiled step is untouched either way (bit-exactness test)
    profiler = obs_api.make_profiler(
        strat.observability.profile or args.profile_steps > 0,
        window=args.profile_steps, trace_dir=args.profile_trace_dir)
    sink.emit("run_meta", steps=args.steps, arch=args.arch,
              smoke=bool(args.smoke), n_workers=W, start_step=start,
              strategy_json=strat.to_dict(),
              obs_metrics=strat.observability.metrics)

    if getattr(cfg, "arch_type", "") == "gan":
        it = gan_batch_iterator(args.seed, args.batch, cfg)
    else:
        enc_shape = ((cfg.encdec.enc_seq, cfg.d_model) if cfg.is_encdec
                     else None)
        it = lm_batch_iterator(args.seed, args.batch, args.seq,
                               cfg.vocab_size, enc_shape)
    for _ in range(start):  # keep the data stream aligned across resumes
        next(it)

    history = []
    batch = None
    t0 = time.time()
    wall_series = None
    warm_variants = set()  # do_exchange values whose jit variant compiled
    interval_s = 0.0       # synced wall time since the last timing event
    interval_n = 0
    ctx = jax.set_mesh(mesh) if mesh is not None else nullcontext()
    with ctx:
        for i in range(start, args.steps):
            with obs_api.step_span(i, obs_spans):
                with profiler.phase("data", obs_spans):
                    batch = next(it)
                do_exchange = sched.is_exchange_step(i)
                # every step is timed against a device sync: `dispatch` is
                # the call that enqueues the step, `sync` the wait for its
                # metrics; an unsynced perf_counter delta would only
                # measure dispatch
                it_t0 = time.perf_counter()
                with profiler.phase("dispatch", obs_spans):
                    out = step(state, batch, key, do_exchange)
                state = out.state
                with profiler.phase("sync", obs_spans):
                    jax.block_until_ready(out.metrics)
                step_s = time.perf_counter() - it_t0
                profiler.record_step(i, step_s, do_exchange)
                interval_s += step_s
                interval_n += 1
                if wall_series is None and (do_exchange in warm_variants
                                            or i == args.steps - 1):
                    # base compute time from the first step whose jit
                    # variant already compiled (holds across resumes too);
                    # feeds the simulated (straggler-aware) wall-clock series
                    times = sstrag.step_times(profile, W, args.steps,
                                              args.seed, base=step_s)
                    wall_series = sclock.simulate(
                        sched, times, t_ex, strat.participation.fraction,
                        args.seed)["per_step_s"]
                    if i > start:  # backfill the steps already run
                        ledger.tick(0, wall_s=float(
                            wall_series[start:i].sum()))
                warm_variants.add(do_exchange)
                wall = (float(wall_series[i]) if wall_series is not None
                        else 0.0)
                ledger.tick(exchanged=do_exchange, wall_s=wall,
                            participants=n_part)
                if i % args.log_every == 0 or i == args.steps - 1:
                    with profiler.phase("eval", obs_spans):
                        m = jax.device_get(out.metrics)
                    rec = {"step": i, "round": sched.round_index(i),
                           **({"participants": n_part}
                              if n_part is not None else {}),
                           "loss": float(m["loss"]),
                           "grad_norm": float(m["grad_norm"]),
                           "error_norm": float(m["error_norm"]),
                           **({"staleness_max": float(m["staleness_max"]),
                               "staleness_mean": round(
                                   float(m["staleness_mean"]), 2)}
                              if strat.schedule.kind == "delayed" else {}),
                           "wire_mb_step": round(
                               ledger.wire_bytes_per_step / 1e6, 3),
                           "cum_wire_mb": round(
                               ledger.cumulative_wire_bytes / 1e6, 2),
                           "comm_ratio": round(ledger.compression_ratio, 2),
                           "sim_clock_s": round(ledger.sim_clock_s, 3),
                           "elapsed_s": round(time.time() - t0, 1)}
                    history.append(rec)
                    sink.emit("train_log", **rec)
                    sink.emit("timing", step=i, step_s=round(step_s, 6),
                              interval_s=round(interval_s, 6),
                              steps_in_interval=interval_n)
                    interval_s = 0.0
                    interval_n = 0
                    if "obs" in m:
                        sink.emit("obs_metrics", step=i, **m["obs"])
                if (args.checkpoint and args.checkpoint_every
                        and (i + 1) % args.checkpoint_every == 0
                        and i != args.steps - 1):
                    save_ckpt(args.checkpoint, state, i + 1)
        if profiler.step_walls:
            # close the profiled window (still under the mesh context —
            # the re-lowering below needs it). With spans on, the
            # optimized HLO carries the repro.obs scope metadata, giving
            # the profile event its device-phase attribution.
            hlo_txt = ""
            if obs_spans:
                hlo_txt = step.lower(state, batch, key,
                                     do_exchange).compile().as_text()
            profiler.emit(sink, hlo_text=hlo_txt)
    sink.emit("comm_summary", **ledger.summary())
    sink.close()
    if args.checkpoint:
        save_ckpt(args.checkpoint, state,
                  int(jax.device_get(state.step)))
        print(f"saved DQState to {args.checkpoint}")
    return TrainRun(history, step, state, batch, key, mesh)


def gan_batch_iterator(seed, batch, cfg):
    """Procedural-image batches for the GAN archs: {"real"} (dcgan32), and
    {"real", "label"} for a class-conditional one (biggan128)."""
    from repro.data import labelled_images, procedural_images

    key = jax.random.key(seed)
    i = 0
    while True:
        k = jax.random.fold_in(key, i)
        if getattr(cfg, "n_classes", 0):
            real, label = labelled_images(k, batch, cfg.image_size,
                                          cfg.channels, cfg.n_classes)
            yield {"real": real, "label": label}
        else:
            yield {"real": procedural_images(k, batch, cfg.image_size,
                                             cfg.channels)}
        i += 1


if __name__ == "__main__":
    main()
