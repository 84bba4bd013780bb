"""The benchmark: one cell of BENCHMARK.json, one run.

    python bench/run.py --workload dcgan32.q8.b64 --seed 7 --seconds 10 \
        --trace 0

A cell is a configuration (`bench/configs/<config>.json`) under a traffic mix
(`bench/traffic/<traffic>.json`), with the limits of its comparison in
`bench/limits/<cell>.json`; the per-layer metrics are readers in
`bench/metrics/<metric>.py`. All are found by the names in BENCHMARK.json.
The configuration names its model family (`"family"`), a module in
`bench/families/<family>.py` that gives everything model-specific: the
program's model config, the data, the plain reference of the field, the
model FLOPs and the parameter sizes. The rest of the harness knows no
model.

Set-up builds the training step as the launcher does (`program.py`), makes
the pool of batches from the seed (`pool.py`, each batch drawn by the
family), sets the optimistic term the reference also starts from, and takes
the first three steps through the window's own call, keeping the state
after steps 0, 1 and 3 on the host for the comparison. It warms up, then
measures for --seconds seconds: each step is the launcher's loop body,
synced on its metrics. With --trace 1 it measures untraced for the dispatch
time and traces the window's last second with the profiler, for the
per-layer metrics. After the window it reads the peak device memory, frees
the program, runs the plain reference (`reference.py`, with the family's
model) over the same three steps and compares (`check.py`).

The last line of standard output is one JSON object: correct, attempted,
failed, metrics, device (and breakdown with --trace 1), and last the
numbers compared with their limits, which also end standard error. Without
a TPU, or with fewer chips than the cell asks for, it exits 1 and prints no
such line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [p for p in (BENCH, os.path.join(ROOT, "src"))
                if p not in sys.path]

WARMUP_STEPS = 5      # after the three compared steps, before the window
TRACE_SECONDS = 1.0   # --trace 1: the traced end of the window
COMPARED_STEPS = 3


class BenchError(Exception):
    pass


def load_json(path):
    with open(path) as fh:
        return json.load(fh)


def load_module(path: str, name: str):
    import importlib.util

    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def _family_module(path: str):
    """One module object per family file and process, so that functions
    the family hands to `jax.jit` as static arguments keep their identity
    (and their compiled programs) from call to call."""
    name = os.path.splitext(os.path.basename(path))[0]
    return load_module(path, f"bench_family_{name}")


def load_family(root: str, config: dict, path: str):
    """The model family module that `config` (read from `path`) names in
    its `"family"` key, loaded by path from `bench/families/` under `root`.
    A configuration without the key, or naming no family found there, is
    an error that names the file and the families found."""
    fams = os.path.join(root, "bench", "families")
    found = sorted(f[:-3] for f in os.listdir(fams)
                   if f.endswith(".py")) if os.path.isdir(fams) else []
    name = config.get("family")
    if name not in found:
        what = ("has no \"family\" key" if name is None
                else f"names an unknown family {name!r}")
        raise BenchError(f"{path} {what}; families found in "
                         f"{fams}: {found}")
    return _family_module(os.path.join(fams, name + ".py"))


def resolve(root: str, workload: str) -> dict:
    """Everything a cell's run needs, found by the names in the manifest
    at `root`."""
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json; "
                         f"have {sorted(cells)}")
    cell = cells[workload]
    bench = os.path.join(root, "bench")
    configs = {c["name"]: c for c in manifest["configs"]}

    def for_cell(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]

    config_path = os.path.join(root, configs[cell["config"]]["file"])
    config = load_json(config_path)
    return {
        "cell": cell,
        "config": config,
        "family": load_family(root, config, config_path),
        "traffic": load_json(os.path.join(bench, "traffic",
                                          cell["traffic"] + ".json")),
        "limits": load_json(os.path.join(bench, "limits",
                                         workload + ".json")),
        "end_to_end": for_cell(manifest["end_to_end"]),
        "per_layer": for_cell(manifest["per_layer"]),
        "metrics_dir": os.path.join(bench, "metrics"),
    }


def reader(metrics_dir: str, name: str):
    return load_module(os.path.join(metrics_dir, name + ".py"),
                       f"bench_metric_{name.replace('.', '_')}").read


class Compiles:
    """Counts jaxpr traces and backend compiles through jax.monitoring."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n = 0
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        if event in (self.TRACE, self.COMPILE):
            self.n += 1

    def close(self):
        self._jax.monitoring.unregister_event_duration_listener(self._on)


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def tag(dev: dict) -> str:
    return f"[{dev['platform']} {dev['kind']} x{dev['count']}]"


def require_chip(chips: int) -> dict:
    dev = device_info()
    if dev["platform"] != "tpu":
        raise BenchError(f"needs a TPU; JAX found {tag(dev)}")
    if dev["count"] != chips:
        raise BenchError(f"the cell asks for {chips} chip(s); JAX found "
                         f"{tag(dev)}")
    return dev


def host_leaves(tree):
    import jax
    import numpy as np

    return [np.asarray(x) for x in jax.device_get(jax.tree.leaves(tree))]


def applied_update(state, traffic: dict):
    """The first update the optimizer applied, worked out from the state
    after one step, per parameter leaf (float64): each worker sent
    Q(lr * g + 0) = lr * g - e1, the mean of those is what the exchange
    averaged, and two_phase's owners sent that less their residual e2."""
    import numpy as np

    import flops

    W, lr = traffic["workers"], traffic["lr"]
    g = [np.asarray(x, np.float64) for x in host_leaves(state.prev_grad)]
    if traffic["exchange"] == "exact":
        return [lr * x.mean(axis=0) for x in g]
    e1 = [np.asarray(x, np.float64) for x in host_leaves(state.ef["leaf"])]
    q = [(lr * gx - ex).mean(axis=0) for gx, ex in zip(g, e1)]
    if traffic["exchange"] == "two_phase":
        sizes = [x[0].size for x in g]
        for bid, (_, members) in enumerate(flops.bucket_layout(sizes, W)):
            e2 = np.asarray(state.ef["bucket"][str(bid)]["e2"],
                            np.float64).reshape(-1)
            off = 0
            for i in members:
                q[i] = q[i] - e2[off:off + sizes[i]].reshape(q[i].shape)
                off += sizes[i]
    return q


def start_from_probe(prog, spec: dict, seed: int) -> None:
    """Set the program's optimistic term to the seeded one the reference
    starts from (`reference.lookahead_probe`), each leaf placed as the
    program's own."""
    import jax

    from reference import lookahead_probe

    traffic = spec["traffic"]
    probe = lookahead_probe(spec["family"], spec["config"], seed,
                            traffic["workers"], traffic["lr"])
    leaves, treedef = jax.tree.flatten(prog.state.prev_grad)
    placed = [jax.device_put(p.reshape(x.shape).astype(x.dtype), x.sharding)
              for p, x in zip(probe, leaves)]
    prog.state = prog.state._replace(
        prev_grad=jax.tree.unflatten(treedef, placed))


def first_steps(prog, pool, spec: dict, seed: int) -> dict:
    """The compared steps, through the window's own call and feed, from the
    seeded optimistic term: the losses, the first applied update, and the
    parameters before the first step and after the last (before the next
    step donates them)."""
    import jax

    traffic = spec["traffic"]
    start_from_probe(prog, spec, seed)
    snap = {"w0": host_leaves(prog.state.params), "losses": []}
    for i in range(COMPARED_STEPS):
        m, _, _ = prog.step_once(pool[i])
        snap["losses"].append(float(jax.device_get(m["loss"])))
        if i == 0:
            snap["q1"] = applied_update(prog.state, traffic)
    snap["w3"] = host_leaves(prog.state.params)
    return snap


def reference_batches(spec: dict, seed: int) -> dict:
    """The compared steps' rows, made anew from the seed as the pool makes
    them: each key of the batch dict stacked into (steps, W, batch, ...)."""
    import numpy as np

    traffic = spec["traffic"]
    W, B = traffic["workers"], traffic["batch_per_worker"]
    pool = spec["family"].make_pool(spec["config"], seed,
                                    traffic["pool_batches"], W * B)
    return {k: np.stack([np.asarray(b[k]).reshape((W, B) + b[k].shape[1:])
                         for b in pool[:COMPARED_STEPS]])
            for k in pool[0]}


def timed_loop(prog, pool, seconds: float):
    """Launcher steps until `seconds` have passed. Returns (walls, dispatch
    times, seconds from the first step's call to the last one's sync)."""
    walls, disp = [], []
    t0 = time.perf_counter()
    while True:
        _, d, w = prog.step_once(pool[prog.i % len(pool)])
        walls.append(w)
        disp.append(d)
        now = time.perf_counter()
        if now - t0 >= seconds:
            return walls, disp, now - t0


def breakdown(tr: dict) -> dict:
    """The device ops that took most of the traced window (seconds per
    chip, keyed by HLO name and scope), and the device's idle time by the
    benchmark's host span open during it."""
    import tracefmt as T

    chips = T.chips_seen(tr)
    w0, w1 = tr["window_ns"]
    per_op = {}
    for c, name, s, d in tr["ops"]:
        inside = min(s + d, w1) - max(s, w0)
        if inside <= 0:
            continue
        scope = tr["hlo"].get(name, {}).get("op_name", "")
        key = f"{name} {scope.split('/', 1)[-1]}".strip()
        per_op[key] = per_op.get(key, 0.0) + inside * 1e-9 / len(chips)
    idle = {}
    for c in chips:
        gaps = T.subtract([(w0, w1)], T.chip_ops(tr, c))
        for a, b in gaps:
            covered = 0.0
            for name, s, d in tr["host"]:
                ov = min(b, s + d) - max(a, s)
                if ov > 0:
                    idle[name] = idle.get(name, 0.0) + ov * 1e-9 / len(chips)
                    covered += ov
            if b - a > covered:
                idle["no bench span"] = idle.get("no bench span", 0.0) + (
                    b - a - covered) * 1e-9 / len(chips)
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in gaps]}


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             dev: dict, t_start: float = T_START, step_fault=None) -> dict:
    """One run of a resolved cell on the devices JAX holds. `step_fault`,
    for tests, wraps the built program before its first step."""
    import jax
    import numpy as np

    import check
    import tracefmt
    from program import build_program
    from reference import run_reference

    cfg, traffic, fam = spec["config"], spec["traffic"], spec["family"]
    chips, W = spec["cell"]["chips"], traffic["workers"]
    if W != chips:
        raise BenchError(f"traffic has {W} workers for {chips} chip(s)")
    rows = traffic["batch_per_worker"] * W
    marks = [("start", t_start), ("imports", time.perf_counter())]
    prog = build_program(fam, cfg, traffic, seed, chips)
    if step_fault is not None:
        step_fault(prog)
    marks.append(("build", time.perf_counter()))
    pool = fam.make_pool(cfg, seed, traffic["pool_batches"], rows)
    jax.block_until_ready(pool)
    marks.append(("pool", time.perf_counter()))
    compiles = Compiles()
    try:
        with prog.context():
            snap = first_steps(prog, pool, spec, seed)
            marks.append(("compared steps", time.perf_counter()))
            for _ in range(WARMUP_STEPS):
                prog.step_once(pool[prog.i % len(pool)])
            # set-up's objects out of the collector's way during the window
            gc.collect()
            gc.freeze()
            setup_s = time.perf_counter() - t_start
            marks.append(("warm-up", t_start + setup_s))
            n_before = compiles.n
            ctx = None
            if not trace:
                walls, disp, window_s = timed_loop(prog, pool, seconds)
                attempted = len(walls)
            else:
                _, disp, _ = timed_loop(
                    prog, pool, max(seconds - TRACE_SECONDS, TRACE_SECONDS))
                trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
                try:
                    jax.profiler.start_trace(trace_dir)
                    try:
                        with jax.profiler.TraceAnnotation("bench/window"):
                            walls, _, window_s = timed_loop(
                                prog, pool, TRACE_SECONDS)
                    finally:
                        jax.profiler.stop_trace()
                    xplane = tracefmt.xplane_path(trace_dir)
                    window_compiles = compiles.n - n_before
                    hlo = prog.step.lower(
                        prog.state, pool[0], prog.key,
                        prog.sched.is_exchange_step(prog.i)).compile()
                    tr = tracefmt.compact(xplane, hlo.as_text(), len(walls),
                                          chips)
                finally:
                    shutil.rmtree(trace_dir, ignore_errors=True)
                attempted = len(disp) + len(walls)
                ctx = {"trace": tr, "config": cfg, "family": fam,
                       "traffic": traffic, "dispatch_s": disp,
                       "window_s": window_s, "steps": len(walls),
                       "chips": chips}
            if not trace:
                window_compiles = compiles.n - n_before
            last = jax.device_get(prog.state.params)
            nonfinite = int(sum(np.size(x) - np.isfinite(x).sum()
                                for x in jax.tree.leaves(last)))
    finally:
        gc.unfreeze()
        compiles.close()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())
    device = dict(dev, memory_peak_bytes=int(peak))

    metrics = {}
    if not trace:
        values = {"samples_per_s": len(walls) * rows / window_s,
                  "step_ms_p95": float(np.percentile(walls, 95)) * 1e3,
                  "setup_s": setup_s}
        for m in spec["end_to_end"]:
            if m["name"] not in values:
                raise BenchError(f"no end-to-end metric {m['name']!r}")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    out = {"metrics": metrics, "device": device, "attempted": attempted,
           "failed": 0}
    if trace:
        import tracefmt as T

        peaks = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
        if dev["kind"] not in peaks:
            raise BenchError(f"no peaks for device kind {dev['kind']!r} "
                             "in bench/peaks.json")
        ctx["peaks"] = peaks[dev["kind"]]
        for m in spec["per_layer"]:
            value = reader(spec["metrics_dir"], m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        w0, w1 = tr["window_ns"]
        busy = [T.length(T.chip_ops(tr, c)) for c in T.chips_seen(tr)]
        device["busy_s"] = sum(busy) / len(busy) * 1e-9
        device["window_s"] = (w1 - w0) * 1e-9
        out["breakdown"] = breakdown(tr)

    del prog, pool, last
    gc.collect()
    t_ref = time.perf_counter()
    ref = run_reference(fam, cfg, traffic, seed,
                        reference_batches(spec, seed), W)
    out["setup_phases_s"] = {b[0]: b[1] - a[1] for a, b in zip(marks,
                                                               marks[1:])}
    out["setup_phases_s"]["reference, after the window"] = \
        time.perf_counter() - t_ref
    values = check.numbers(snap, ref, spec["limits"])
    values["window_compiles"] = window_compiles
    values["window_nonfinite"] = nonfinite
    out["correct"], out["checks"] = check.judge(values, spec["limits"])
    return out


def emit(out: dict) -> None:
    """Print a run's result: the compile count, then each number compared
    beside its limit (the last lines of standard error), then the result's
    line, whose last key holds the same numbers."""
    checks = out.pop("checks")
    t = tag(out["device"])
    print(f"# {t} seconds by phase: {out.pop('setup_phases_s')}",
          flush=True)
    print(f"# {t} compiles inside the window: "
          f"{checks['window_compiles']['value']}", flush=True)
    for name, c in checks.items():
        print(f"{t} {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": out["metrics"],
            "device": out["device"]}
    if "breakdown" in out:
        line["breakdown"] = out["breakdown"]
    line["checks"] = checks
    print(json.dumps(line), flush=True)


def use_compile_cache() -> str:
    """JAX's persistent compile cache at a fixed path in the checkout, for
    every program of the run, the reference's too. Call before importing
    JAX."""
    cache = os.path.join(ROOT, ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        use_compile_cache()
        spec = resolve(ROOT, args.workload)
        dev = require_chip(spec["cell"]["chips"])
        print(f"# {tag(dev)} cell {spec['cell']['name']} seed {args.seed}",
              flush=True)
        out = run_cell(spec, args.seed, args.seconds, bool(args.trace), dev)
    except (BenchError, OSError, KeyError, ValueError) as e:
        print(f"bench: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    emit(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
