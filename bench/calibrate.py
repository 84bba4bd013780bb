"""The readings the limits of `bench/limits/<cell>.json` are set from.

    python bench/calibrate.py --workload dcgan32.q8.b64 \
        --seeds 1,2,3,4,5,6,7,8,9,10,11,12 --control-seeds 1,2,3

For each of --seeds, the program's compared numbers, as a benchmark run
reads them (the lower readings). For each of --control-seeds, the same
numbers of the reference put in the program's place (a) with its field
computed in a lower precision (`CONTROLS`, see `reference.py`), and (b)
with each fault the cell can have planted (`reference.FAULTS`): half of
each worker's batch left out with the mean taken over the rest, the OMD
lookahead dropped, the update applied with its sign flipped, and, on
several chips, the exchange left out (each worker applies its own update).
A state left unchanged reads 1 by the measure of `check.py` and needs no
run. Every reading is also judged against the cell's limits as they stand,
as a run's comparison judges it: `correct` is printed beside it, and a
control or fault that reads `correct` true is named at the end. One JSON
line per reading; the last line sums them up per number: the largest
program reading and the smallest of each of the others.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

import run as R

CONTROLS = ("bfloat16", "float8_e4m3fn")


def program_numbers(spec, seed):
    from program import build_program
    from reference import run_reference

    import check

    traffic, cfg, fam = spec["traffic"], spec["config"], spec["family"]
    W = traffic["workers"]
    prog = build_program(fam, cfg, traffic, seed, spec["cell"]["chips"])
    pool = fam.make_pool(cfg, seed, traffic["pool_batches"],
                         W * traffic["batch_per_worker"])
    with prog.context():
        snap = R.first_steps(prog, pool, spec, seed)
    del prog, pool
    gc.collect()
    ref = run_reference(fam, cfg, traffic, seed,
                        R.reference_batches(spec, seed), W)
    return check.readings(snap, ref), ref


def reference_numbers(spec, seed, ref, **kw):
    import check
    from reference import run_reference

    traffic = spec["traffic"]
    alt = run_reference(spec["family"], spec["config"], traffic, seed,
                        R.reference_batches(spec, seed), traffic["workers"],
                        **kw)
    return check.readings(alt, ref)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--controls", default=",".join(CONTROLS),
                    help="the lower precisions to read, of "
                    + ", ".join(CONTROLS))
    ap.add_argument("--faults", default=None,
                    help="the faults to read (default: every one the cell "
                    "can have; empty: none)")
    args = ap.parse_args(argv)
    R.use_compile_cache()
    spec = R.resolve(R.ROOT, args.workload)
    dev = R.require_chip(spec["cell"]["chips"])
    import check
    from reference import FAULTS

    tag = f"[{dev['platform']} {dev['kind']} x{dev['count']}]"
    rows, passed = [], []

    def out(kind, seed, reads):
        nums = check.compared(reads, spec["limits"])
        rows.append((kind, {k: v for k, v in reads.items()
                            if isinstance(v, float)}))
        correct, _ = check.judge(nums, spec["limits"])
        if correct != (kind == "program"):
            passed.append((kind, seed, correct))
        print(json.dumps({"kind": kind, "seed": seed, "numbers": nums,
                          "readings": reads, "correct": correct,
                          "device": dev}), flush=True)

    refs = {}
    for s in [int(x) for x in args.seeds.split(",")]:
        nums, refs[s] = program_numbers(spec, s)
        out("program", s, nums)
    faults = [f for f in FAULTS
              if f != "no_exchange" or spec["traffic"]["workers"] > 1]
    if args.faults is not None:
        faults = [f for f in args.faults.split(",") if f in faults]
    for s in [int(x) for x in args.control_seeds.split(",")]:
        ref = refs.get(s) or program_numbers(spec, s)[1]
        for control in filter(None, args.controls.split(",")):
            out(f"control_{control}", s,
                reference_numbers(spec, s, ref, control=control))
        for f in faults:
            out(f, s, reference_numbers(spec, s, ref, fault=f))
    summary = {}
    for kind, nums in rows:
        for k, v in nums.items():
            cur = summary.setdefault(kind, {}).get(k)
            better = max if kind == "program" else min
            summary[kind][k] = v if cur is None else better(cur, v)
    print(f"# summary {tag}; readings judged against the limits the other "
          f"way than they should be: {passed or 'none'}", file=sys.stderr)
    print(json.dumps({"summary": summary, "misjudged": passed,
                      "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
