"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes, prove the sharding config is coherent, and dump the
roofline ingredients (FLOPs, bytes, per-category collective bytes, memory
analysis) to experiments/dryrun/*.json.

Single combo:
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-2b \
        --shape train_4k --mesh single
Full sweep (subprocess per combo, cached by output file):
    PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh both]
"""
import argparse
import json
import os
import re
import subprocess
import sys
import time

# 512 forced CPU host devices stand in for the production meshes. Set in
# main(), before the first jax call (jax fixes the device count at its
# first init), and never at import: importers keep their own XLA_FLAGS.
HOST_ENV = {"JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=512"}

# v5e roofline constants (per chip)
PEAK_FLOPS = 197e12      # bf16
HBM_BW = 819e9           # bytes/s
ICI_BW = 50e9            # bytes/s/link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}
_COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")
_TYPE_RE = re.compile(r"(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64|f64)"
                      r"\[([0-9,]*)\]")


def parse_collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in the (partitioned,
    per-device) optimized HLO, keyed by op kind and element type."""
    out: dict = {}
    for line in hlo_text.splitlines():
        m = re.search(r"=\s*([^=]*?)\s*(" + "|".join(_COLL_OPS) +
                      r")(-start)?\(", line)
        if not m or "-done(" in line:
            continue
        result_types, op = m.group(1), m.group(2)
        nbytes = 0
        int8 = 0
        for dt, dims in _TYPE_RE.findall(result_types):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            b = n * _DTYPE_BYTES[dt]
            nbytes += b
            if dt in ("s8", "u8", "pred"):
                int8 += b
        rec = out.setdefault(op, {"count": 0, "bytes": 0, "int8_bytes": 0})
        rec["count"] += 1
        rec["bytes"] += nbytes
        rec["int8_bytes"] += int8
    return out


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS: 6·N·D train (fwd+bwd), 2·N·D forward-only; N active for
    MoE. D = tokens processed per step (whole job, all chips)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def attention_flops(cfg, shape) -> float:
    """Analytic attention FLOPs (not covered by 6·N·D): 4·tokens·Keff·H·hd
    per attention layer forward (QKᵀ + PV), ×3 with backward for training.
    Keff = average attended keys (causal ≈ S/2, bounded by the window)."""
    n_attn = sum(1 for i in range(cfg.num_layers)
                 if cfg.layer_pattern[i % len(cfg.layer_pattern)] == "attn")
    if cfg.is_encdec:
        n_attn += cfg.encdec.enc_layers + cfg.num_layers  # enc self + cross
    if n_attn == 0:
        return 0.0
    S = shape.seq_len
    H, hd = cfg.num_heads, cfg.head_dim
    win = cfg.attention_window
    if shape.kind == "decode":
        keff = min(S, win) if win else S
        tokens = shape.global_batch
        mult = 1.0
    else:
        keff = min(S / 2, win) if win else S / 2
        tokens = shape.global_batch * S
        mult = 3.0 if shape.kind == "train" else 1.0
    return mult * 4.0 * tokens * keff * H * hd * n_attn


def analytic_flops(cfg, shape) -> float:
    return model_flops(cfg, shape) + attention_flops(cfg, shape)


def applicable(cfg, shape) -> tuple:
    """(runs?, reason)."""
    if shape.name == "long_500k" and not cfg.supports_long_context:
        return False, ("pure full-attention arch: 500k decode requires "
                       "sub-quadratic attention (DESIGN.md §long_500k)")
    return True, ""


# --------------------------------------------------------------------------- #
def run_combo(arch: str, shape_name: str, multi_pod: bool, *,
              exchange: str, compressor: str, optimizer: str,
              extrapolation: str, layout: str = "auto",
              out_path: str = None, tag: str = "",
              lower_only: bool = False, moe_dispatch: str = "",
              remat: str = "", ef_dtype: str = "bfloat16",
              kv_layout: str = "hd_model", mesh_override: str = "") -> dict:
    import jax
    import jax.numpy as jnp

    import repro.configs as cfgs
    from repro.configs.base import DQConfig, SHAPES
    from repro.core.dqgan import DQGAN
    from repro.launch import specs as S
    from repro.launch.mesh import make_production_mesh, worker_axes_for
    from repro.models import build
    from repro.parallel import sharding as shd

    import dataclasses as _dc

    cfg = cfgs.get(arch)
    if moe_dispatch and cfg.moe is not None:
        cfg = _dc.replace(cfg, moe=_dc.replace(cfg.moe, dispatch=moe_dispatch))
    if remat:
        cfg = _dc.replace(cfg, remat=remat)
    shape = SHAPES[shape_name]
    ok, reason = applicable(cfg, shape)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "exchange": exchange, "compressor": compressor,
        "optimizer": optimizer, "layout": layout, "tag": tag,
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
    }
    if not ok:
        rec.update(status="skip", reason=reason)
        return _finish(rec, out_path)

    if layout == "auto":
        layout = "fsdp" if cfg.param_count() > 10e9 else "dp"
        rec["layout"] = layout

    mesh = make_production_mesh(multi_pod=multi_pod, override=mesh_override)
    if mesh_override:
        rec["mesh"] = mesh_override.replace(",", "x")
    n_chips = mesh.size
    rec["chips"] = n_chips
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            max_seq = shape.seq_len if not cfg.use_rope else 0
            params_sds, pspecs = S.abstract_params(cfg, mesh, layout,
                                                   max_seq or 8)
            bundle = build(cfg)
            if shape.kind == "train":
                waxes = worker_axes_for(layout, multi_pod)
                spmd = "shard_map"
                if layout == "fsdp" and multi_pod:
                    # XLA's SPMD partitioner CHECK-fails on shard_map manual
                    # over 'pod' with FSDP auto axes inside (DESIGN.md §2);
                    # the vmap worker formulation is semantics-identical.
                    spmd = "vmap"
                    exchange = "sim"
                    rec["exchange"] = "sim(vmap)"
                dq = DQConfig(
                    compressor=compressor, exchange=exchange,
                    optimizer=optimizer, extrapolation=extrapolation,
                    worker_axes=waxes, ef_dtype=ef_dtype, spmd=spmd,
                )
                # shard_map manual specs use worker axes only; jit-level
                # batch sharding spans all data axes.
                manual_bspec = jax.sharding.PartitionSpec(waxes) if waxes \
                    else jax.sharding.PartitionSpec()
                trainer = DQGAN(field_fn=bundle.field_fn, dq=dq, mesh=mesh,
                                param_specs=pspecs, batch_spec=manual_bspec)
                state_sds = trainer.init_abstract(params_sds)
                batch_sds = S.train_batch_specs(cfg, shape, mesh)
                rec["n_workers"] = trainer.n_workers
                lowered = jax.jit(trainer.step).lower(
                    state_sds, batch_sds, S.key_spec())
            elif shape.kind == "prefill":
                args = S.prefill_input_specs(cfg, shape, mesh)
                lowered = jax.jit(bundle.prefill).lower(params_sds, *args)
            else:  # decode
                rec["kv_layout"] = kv_layout
                tokens, caches = S.decode_input_specs(cfg, shape, mesh,
                                                      kv_layout)
                lowered = jax.jit(bundle.decode_step).lower(
                    params_sds, tokens, caches)
            rec["lower_s"] = round(time.time() - t0, 2)
            if lower_only:
                rec["status"] = "lowered"
                return _finish(rec, out_path)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 2)

            ca = compiled.cost_analysis() or {}
            rec["flops"] = float(ca.get("flops", 0.0))
            rec["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
            try:
                ma = compiled.memory_analysis()
                if ma is not None:
                    rec["memory_analysis"] = {
                        k: int(getattr(ma, k))
                        for k in ("argument_size_in_bytes",
                                  "output_size_in_bytes",
                                  "temp_size_in_bytes",
                                  "generated_code_size_in_bytes")
                        if hasattr(ma, k)
                    }
            except Exception as e:  # pragma: no cover
                rec["memory_analysis_error"] = str(e)
            hlo = compiled.as_text()
            rec["collectives"] = parse_collective_bytes(hlo)
            rec["hlo_bytes"] = len(hlo)
            # while-trip-corrected accounting (cost_analysis counts loop
            # bodies once — see EXPERIMENTS.md §Dry-run/validity)
            try:
                from repro.launch.hlo_analysis import analyze
                rec["corrected"] = analyze(hlo)
            except Exception as e:  # pragma: no cover
                rec["corrected_error"] = str(e)[:500]

            # ---- roofline terms (per-chip; see benchmarks/roofline.py) --- #
            rec["mf"] = model_flops(cfg, shape)
            rec["analytic_flops"] = analytic_flops(cfg, shape)
            corr = rec.get("corrected") or {}
            coll = corr.get("collectives") or rec["collectives"]
            coll_bytes = sum(v["bytes"] for v in coll.values())
            mem_bytes = corr.get("traffic_result_bytes",
                                 rec["bytes_accessed"])
            rec["roofline"] = {
                # analytic per-chip FLOPs: cost_analysis undercounts scan
                # bodies; raw value kept in rec["flops"] for reference
                "compute_s": rec["analytic_flops"] / n_chips / PEAK_FLOPS,
                "memory_s": mem_bytes / HBM_BW,
                "collective_s": coll_bytes / ICI_BW,
            }
            dom = max(rec["roofline"], key=rec["roofline"].get)
            rec["bottleneck"] = dom.replace("_s", "")
            rec["status"] = "ok"
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}"[:2000],
                   elapsed_s=round(time.time() - t0, 2))
    return _finish(rec, out_path)


def _finish(rec, out_path):
    if out_path:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=1)
    print(json.dumps({k: rec.get(k) for k in
                      ("arch", "shape", "mesh", "status", "bottleneck",
                       "compile_s", "reason", "error")}))
    return rec


# --------------------------------------------------------------------------- #
def all_combos(mesh_arg: str):
    import repro.configs as cfgs
    shapes = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[mesh_arg]
    for arch in list(cfgs.ASSIGNED) + ["gemma-2b-swa"]:
        for sh in shapes:
            for mp in meshes:
                yield arch, sh, mp


def driver(args):
    outdir = args.outdir
    os.makedirs(outdir, exist_ok=True)
    combos = list(all_combos(args.mesh))
    todo = []
    for arch, sh, mp in combos:
        name = f"{arch}__{sh}__{'multi' if mp else 'single'}"
        if args.tag:
            name += f"__{args.tag}"
        path = os.path.join(outdir, name + ".json")
        if os.path.exists(path) and not args.force:
            with open(path) as f:
                if json.load(f).get("status") in ("ok", "skip"):
                    continue
        todo.append((arch, sh, mp, path))
    print(f"{len(combos)} combos, {len(todo)} to run", flush=True)
    procs: list = []
    for arch, sh, mp, path in todo:
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", arch, "--shape", sh,
               "--mesh", "multi" if mp else "single",
               "--exchange", args.exchange, "--compressor", args.compressor,
               "--optimizer", args.optimizer,
               "--extrapolation", args.extrapolation,
               "--layout", args.layout, "--out", path, "--tag", args.tag]
        while len([p for p in procs if p.poll() is None]) >= args.jobs:
            time.sleep(5)
        procs = [p for p in procs if p.poll() is None]
        print("RUN", arch, sh, "multi" if mp else "single", flush=True)
        procs.append(subprocess.Popen(cmd, env=dict(os.environ, **HOST_ENV)))
    for p in procs:
        p.wait()
    # summary
    ok = err = skip = 0
    for fn in sorted(os.listdir(outdir)):
        if fn.endswith(".json"):
            with open(os.path.join(outdir, fn)) as f:
                st = json.load(f).get("status")
            ok += st == "ok"
            err += st == "error"
            skip += st == "skip"
    print(f"done: ok={ok} skip={skip} error={err}")


def main():
    os.environ.update(HOST_ENV)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--exchange", default="two_phase")
    ap.add_argument("--compressor", default="qsgd8_linf")
    ap.add_argument("--optimizer", default="omd")
    ap.add_argument("--extrapolation", default="local")
    ap.add_argument("--layout", default="auto")
    ap.add_argument("--outdir", default="experiments/dryrun")
    ap.add_argument("--out", default=None)
    ap.add_argument("--tag", default="")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--jobs", type=int, default=2)
    ap.add_argument("--lower-only", action="store_true")
    ap.add_argument("--moe-dispatch", default="")
    ap.add_argument("--remat", default="")
    ap.add_argument("--ef-dtype", default="bfloat16")
    ap.add_argument("--kv-layout", default="hd_model")
    ap.add_argument("--mesh-override", default="")
    args = ap.parse_args()
    if args.all:
        driver(args)
        return
    out = args.out
    if out is None:
        name = f"{args.arch}__{args.shape}__{args.mesh}"
        if args.tag:
            name += f"__{args.tag}"
        out = os.path.join(args.outdir, name + ".json")
    run_combo(args.arch, args.shape, args.mesh == "multi",
              exchange=args.exchange, compressor=args.compressor,
              optimizer=args.optimizer, extrapolation=args.extrapolation,
              layout=args.layout, out_path=out, tag=args.tag,
              lower_only=args.lower_only, moe_dispatch=args.moe_dispatch,
              remat=args.remat, ef_dtype=args.ef_dtype,
              kv_layout=args.kv_layout, mesh_override=args.mesh_override)


if __name__ == "__main__":
    main()
