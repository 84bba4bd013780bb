"""Serving launcher: thin CLI over the repro.serve continuous-batching
engine (paged KV cache, floor-bucket prefill, optional quantized weights).

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --smoke \
        --batch 4 --prompt-len 32 --gen 16

The old launcher re-jitted prefill and decode inside every generate()
call (and re-derived the cache length per call as S + gen_steps + 1);
the engine compiles each shape exactly once — pass --assert-single-trace
to make the process fail if a decode retrace ever happens.
"""
from __future__ import annotations

import argparse
import json
import time

import jax

import repro.configs as cfgs
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as lm
from repro.serve import (
    Engine,
    Request,
    SequentialGenerator,
    ServeConfig,
    cdiv,
)
from repro.strategy.components import Compression


def build_serve_config(prompt_len: int, gen: int, batch: int) -> ServeConfig:
    """Shapes sized to the workload: enough blocks for `batch` concurrent
    requests of this prompt/gen length, buckets no larger than the prompt
    (floor-bucket prefill)."""
    bs = 16
    need = max(prompt_len + gen - 1, 1)
    mbps = max(cdiv(need, bs), 1)
    buckets = tuple(b for b in (16, 32, 64, 128, 256, 512)
                    if b <= max(prompt_len, 16))
    return ServeConfig(
        max_batch=batch,
        block_size=bs,
        num_blocks=batch * mbps + 2,
        max_blocks_per_seq=mbps,
        prompt_buckets=buckets,
    )


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="requests (and engine decode slots)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sequential", action="store_true",
                    help="batch-1 baseline instead of the batching engine")
    ap.add_argument("--quantize-weights", default=None, metavar="COMPRESSOR",
                    help="serve quantized weights, e.g. qsgd8_linf")
    ap.add_argument("--weight-plan", default="none",
                    help="per-bucket bit plan: none|uniform|size_tiered|"
                         "delta_budget")
    ap.add_argument("--weight-budget-mb", type=float, default=0.0)
    ap.add_argument("--assert-single-trace", action="store_true",
                    help="fail if the decode step compiled more than once")
    args = ap.parse_args(argv)

    cfg = cfgs.get(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    key = jax.random.key(args.seed)
    params = lm.init(key, cfg, 0)
    scfg = build_serve_config(args.prompt_len, args.gen, args.batch)

    compression = None
    if args.quantize_weights:
        compression = Compression(compressor=args.quantize_weights,
                                  plan=args.weight_plan,
                                  budget_mb=args.weight_budget_mb)

    prompts = jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab_size)
    requests = [Request(rid=i, prompt=prompts[i].tolist(), max_new=args.gen,
                        temperature=args.temperature)
                for i in range(args.batch)]

    if args.sequential:
        runner = SequentialGenerator(cfg, scfg, params,
                                     compression=compression, seed=args.seed)
        t0 = time.time()
        outputs = {r.rid: runner.generate(list(r.prompt), r.max_new,
                                          rid=r.rid,
                                          temperature=r.temperature)
                   for r in requests}
    else:
        runner = Engine(cfg, scfg, params, compression=compression,
                        seed=args.seed)
        t0 = time.time()
        outputs = runner.run(requests)
    dt = time.time() - t0

    stats = runner.stats()
    if args.assert_single_trace:
        assert stats["decode_traces"] == 1, stats
    total = sum(len(v) for v in outputs.values())
    print(json.dumps({
        "arch": cfg.name,
        "mode": "sequential" if args.sequential else "engine",
        "batch": args.batch,
        "prompt_len": args.prompt_len,
        "generated": total,
        "tokens_per_s": round(total / max(dt, 1e-9), 1),
        "decode_traces": stats["decode_traces"],
        "weights": stats["weights"],
        "sample_tokens": outputs[0][:8],
    }))
    return outputs


if __name__ == "__main__":
    main()
