"""Smoke test of the DQGAN trainer on a TPU, through its CLI entry point.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four paper-workers: compressed vs exact

One chip: full-width dcgan32 (1.85 M parameters, batch 64, random weights
from SEED) trained for STEPS steps by `repro.launch.train`, twice:

  (a) ``--preset paper_dqgan`` (the paper's Algorithm 2: OMD, qsgd8 + EF);
  (b) the same with ``--comm-plan uniform --compressor qsgd8_block1024``,
      which puts the fused quantize+EF Pallas kernel on the step.

Each phase must give finite losses and parameters, trace and compile its
step once, and keep doing so over a few more synced steps; phase (b)'s
compiled HLO must hold a ``tpu_custom_call`` (an interpreted kernel leaves
none). Then the compiled kernel runs against `kernels.ref.quantize_ef_ref`
on seeded inputs at the size of a dcgan32 bucket.

Four chips (``--chips 4``) runs only the multi-chip path: W=4 workers under
the compressed exchange (two_phase, bucketed qsgd8_block1024) and under
``exact_baseline``, same seed and steps. Both must trace once and stay
finite; every per-worker state leaf must hold one shard on each chip; the
compressed step must move int8 bytes through its collectives; its error
feedback residuals must stay within one bin of the 8-bit grid; and the two
parameter sets must agree within a few such bins.

Earlier lines report device kind, compile seconds, synced step times and
the checks. The last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU the script exits non-zero and prints no such line. The
compile cache goes where `repro.launch.compile_cache` says.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from contextlib import nullcontext

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

LR = 2e-4
STEPS = 10
SEED = 0
ARCH = ["--arch", "dcgan32", "--batch", "64", "--optimizer", "omd",
        "--lr", str(LR)]
COMPRESSED = ["--comm-plan", "uniform", "--compressor", "qsgd8_block1024"]


class SmokeError(Exception):
    pass


def check(cond, what):
    if not cond:
        raise SmokeError(what)
    print(f"# check ok: {what}", flush=True)


class StepCompiles:
    """Counts traces and backend compiles of the trainer's jitted step
    (`DQGAN.step`) through jax.monitoring, with compile seconds."""

    def __init__(self):
        import jax

        self.traces = 0
        self.compiles = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, secs, **kw):
        name = kw.get("fun_name")
        if event == "/jax/core/compile/jaxpr_trace_duration" and name == "step":
            self.traces += 1
        elif (event == "/jax/core/compile/backend_compile_duration"
              and name == "jit(step)"):
            self.compiles += 1
            self.compile_s += secs

    def snapshot(self):
        return self.traces, self.compiles, self.compile_s


def train_phase(name, argv, counter):
    """Train through `repro.launch.train.run`, then take a few more synced
    steps and lower the step again. Returns (run after the extra steps,
    compiled HLO text, host copy of the state after STEPS steps)."""
    import jax
    import numpy as np

    from repro.launch import train

    t0, c0, s0 = counter.snapshot()
    wall = time.perf_counter()
    run = train.run(argv + ["--steps", str(STEPS), "--log-every", "1",
                            "--seed", str(SEED)])
    wall = time.perf_counter() - wall
    t1, c1, s1 = counter.snapshot()
    losses = [row["loss"] for row in run.history]
    print(f"# {name}: {STEPS} steps in {wall:.3f} s; backend compile "
          f"{s1 - s0:.3f} s over {c1 - c0} compile(s) (0: persistent-cache "
          f"hit); losses {losses}", flush=True)
    check(len(losses) == STEPS and np.all(np.isfinite(losses)),
          f"{name}: {STEPS} finite losses")
    # paper_dqgan and exact_baseline exchange every step: one do_exchange
    # variant, so one trace and at most one backend compile (none on a
    # persistent-cache hit)
    check(t1 - t0 == 1 and c1 - c0 <= 1,
          f"{name}: step traced {t1 - t0}x, compiled {c1 - c0}x")

    trained = jax.device_get(run.state)
    state, times = run.state, []
    with jax.set_mesh(run.mesh) if run.mesh is not None else nullcontext():
        for _ in range(3):
            t = time.perf_counter()
            out = run.step(state, run.batch, run.key, True)
            jax.block_until_ready(out)
            times.append(time.perf_counter() - t)
            state = out.state
        check(counter.snapshot()[:2] == (t1, c1),
              f"{name}: later steps reuse the one trace and compile")
        # lowering traces anew, but must find the executable already built
        hlo = run.step.lower(state, run.batch, run.key,
                             True).compile().as_text()
    print(f"# {name}: synced step seconds {times}", flush=True)
    check(counter.snapshot()[1] == c1,
          f"{name}: the compiled HLO comes from the one compile")
    finite = all(bool(jax.numpy.isfinite(x).all())
                 for x in jax.tree.leaves(state.params))
    check(finite, f"{name}: parameters finite")
    return run._replace(state=state), hlo, trained


def kernel_vs_reference(params):
    """The compiled fused kernel against the jnp reference on one dcgan32
    bucket (the largest of the single-worker uniform layout)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.quantize import bucket_tile_shape, quantize_ef_flat
    from repro.kernels.ref import quantize_ef_ref
    from repro.strategy import Compression

    shapes = jax.tree.map(lambda x: tuple(x.shape), params)
    layout, _ = Compression(plan="uniform", compressor="qsgd8_block1024"
                            ).build(shapes, None, 1)
    n = max(b.size for b in layout.buckets)
    R, C, br = bucket_tile_shape(n)
    k1, k2, k3 = jax.random.split(jax.random.key(SEED), 3)
    g = jax.random.normal(k1, (n,), jnp.float32)
    e = 0.01 * jax.random.normal(k2, (n,), jnp.float32)
    r = jax.random.uniform(k3, (n,), jnp.float32)
    codes, scale, e_new = jax.jit(quantize_ef_flat)(g, e, r)
    codes_r, scale_r, e_new_r = jax.jit(quantize_ef_ref)(
        g.reshape(R, C), e.reshape(R, C), r.reshape(R, C))
    codes = np.asarray(codes, np.int32).reshape(R, C)
    codes_r = np.asarray(codes_r, np.int32)
    scale_err = float(np.max(np.abs(np.asarray(scale)
                                    - np.asarray(scale_r).reshape(R))))
    code_diff = int(np.max(np.abs(codes - codes_r)))
    n_diff = int(np.sum(codes != codes_r))
    bin_max = float(np.max(np.asarray(scale_r))) / 127
    e_err = float(np.max(np.abs(np.asarray(e_new).reshape(R, C)
                                - np.asarray(e_new_r))))
    print(f"# kernel: bucket of {n} elements tiled ({R}, {C}) in blocks of "
          f"{br} rows; max |scale - ref| {scale_err}; codes differing "
          f"{n_diff} of {n} (max step {code_diff}); max |e_new - ref| "
          f"{e_err} (one 8-bit bin: {bin_max})", flush=True)
    # the kernel and XLA may round m / scale differently in the last ulp,
    # which moves a code sitting on a level boundary by one step (and its
    # residual by one bin): allow that for at most 1 element in 10,000
    check(scale_err <= 1e-6 * float(np.max(np.asarray(scale_r)))
          and code_diff <= 1 and n_diff <= n // 10_000
          and e_err <= 1.01 * bin_max,
          "kernel: compiled quantize_ef_flat agrees with quantize_ef_ref")


def one_chip(counter):
    base = ARCH + ["--preset", "paper_dqgan"]
    _, _, trained = train_phase("(a) paper_dqgan", base, counter)
    _, hlo_b, _ = train_phase("(b) paper_dqgan, fused kernel",
                              base + COMPRESSED, counter)
    check("tpu_custom_call" in hlo_b,
          "(b): compiled step holds tpu_custom_call (the fused kernel)")
    kernel_vs_reference(trained.params)


def flat(tree):
    import jax
    import numpy as np

    return np.concatenate([np.ravel(x) for x in jax.tree.leaves(tree)])


def four_chips(counter):
    import jax
    import numpy as np

    import repro.configs as cfgs
    from repro.models import build
    from repro.obs.hlo import collective_summary

    trained, int8 = {}, {}
    for name, argv in (
            ("compressed", ARCH + ["--preset", "paper_dqgan",
                                   "--exchange", "two_phase"] + COMPRESSED),
            ("exact", ARCH + ["--preset", "exact_baseline"])):
        run, hlo, trained[name] = train_phase(f"4-chip {name}", argv,
                                              counter)
        colls = collective_summary(hlo)
        int8[name] = sum(v["int8_bytes"] for v in colls.values())
        print(f"# 4-chip {name}: collectives {colls}", flush=True)
        st = run.state
        per_worker = [x for slot in (st.prev_grad, st.ef, st.sched, st.fsdp)
                      for x in jax.tree.leaves(slot)]
        check(per_worker and all(
            len({s.device for s in x.addressable_shards}) == 4
            and all(s.data.shape[0] == 1 for s in x.addressable_shards)
            for x in per_worker),
            f"4-chip {name}: {len(per_worker)} per-worker leaves, one "
            "worker shard on each of the 4 chips")
    check(int8["compressed"] > 0 and int8["exact"] == 0,
          f"4-chip: int8 collective bytes {int8}")

    # The 8-bit grid of the last exchange. Each element's error is below one
    # bin, max|m| / 127, of its block. Stage 1 quantizes m1 = lr * g + e1
    # per worker, with g the last step's field (kept as prev_grad) and
    # |e1| below one bin of m1, so max|m1| <= lr * G * 127/126. Stage 2
    # quantizes the owner's mean of decoded m1 plus e2: max|m2| <= max|m1|
    # * 127/126. With error feedback the quantization errors telescope: w_c
    # = w_0 - sum_t mean_w lr * g_t + mean_w e1_T + e2_T, so the residual
    # left after any number of steps is below bin1 + bin2. On top of it the
    # runs drift apart by sum lr * (g_exact - g_compressed), the fields
    # taken at points that differ by that residual; allow it as much again.
    st = trained["compressed"]
    G = float(np.max(np.abs(flat(st.prev_grad))))
    bin1 = LR * G / 126
    bin2 = bin1 * 127 / 126
    e1, e2 = (max(float(np.max(np.abs(x))) for path, x in
                  jax.tree_util.tree_leaves_with_path(st.ef)
                  if path[-1].key == k) for k in ("e1", "e2"))
    print(f"# 4-chip: 8-bit bins of the last exchange {bin1}, {bin2} (lr {LR}"
          f" x max|g_T| {G}); max|e1_T| {e1}, max|e2_T| {e2}", flush=True)
    check(e1 <= bin1 and e2 <= bin2,
          "4-chip: error feedback residuals within one 8-bit bin")

    wc, we = flat(st.params), flat(trained["exact"].params)
    w0 = flat(jax.device_get(build(cfgs.get("dcgan32")).init(
        jax.random.key(SEED), max_seq=64)))
    diff = float(np.max(np.abs(wc - we)))
    tol = 2 * (bin1 + bin2)
    gap, moved = float(np.linalg.norm(wc - we)), float(np.linalg.norm(we - w0))
    print(f"# 4-chip: max |w_compressed - w_exact| {diff}, bound {tol} "
          f"(= 2 x (bin1 + bin2)); L2 gap {gap} vs exact run's L2 movement "
          f"{moved}", flush=True)
    check(diff <= tol and gap < moved,
          "4-chip: compressed and exact parameters agree within the bound")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    print(f"# device: {dev}; compile cache {cache}", flush=True)
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev['platform']}",
              file=sys.stderr)
        return 1
    if dev["count"] != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{dev['count']} chip(s)", file=sys.stderr)
        return 1
    counter = StepCompiles()
    try:
        (four_chips if args.chips == 4 else one_chip)(counter)
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
