"""Load-time weight quantization for serving (DESIGN.md §14.4).

Training and inference share one quantization story: the same
`repro.strategy.Compression` component that describes wire compression
describes serving-time weight precision, the same `repro.comm` bucket
layout carves the parameter tree into lane-aligned flat buckets, the same
`plan_comm` planner assigns a per-bucket bit-width (uniform /
size_tiered / delta_budget — per-layer bits via the existing descent),
and the same Pallas `quantize_ef_flat` kernel produces the int8 codes
(run once at load with a zero residual: plain stochastic rounding).

Honored `Compression` fields (the serving contract, DESIGN.md §14.4):
  compressor — must be a linf `StochasticQuant` (any per_block); sets the
               base bit-width. Scale granularity is the kernel's 1024-row
               tiling regardless of per_block (the bucket-native layout).
  plan       — "none"→uniform, else the planner policy verbatim.
  bucket_mb  — f32 MiB per weight bucket.
  budget_mb  — delta_budget target, interpreted as payload MiB of weights.
Ignored: error_feedback / ef_dtype (one-shot quantization carries no
residual stream) and adaptive (no participation axis at serve time).
Buckets the plan leaves at "identity" (size_tiered's small-tensor tier)
stay raw f32.

Quantization is seeded: same params + component + seed → bit-identical
codes, so an engine restart decodes bit-identically (pinned in tests).
Dequantization happens inside the jitted prefill/decode steps
(dequant-on-read): the payload is the traced argument, weights rebuild
per step from int8 codes + f32 scales.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.comm.buckets import BucketLayout, layout_for_params, unpack_into
from repro.comm.planner import CommPlan, plan_comm
from repro.core import compressors as C
from repro.kernels.quantize import bucket_tile_shape, quantize_ef_flat

from .kv_cache import ServeError


@dataclass(frozen=True)
class WeightQuantMeta:
    """Static recipe (jit-safe closure state) for dequantizing a payload."""
    layout: BucketLayout
    plan: CommPlan
    treedef: Any
    leaf_structs: Tuple[Any, ...]       # ShapeDtypeStruct per leaf
    levels: Tuple[int, ...]             # per bucket; 0 = raw f32 bucket
    bits: Tuple[int, ...]               # per bucket; 32 = raw

    @property
    def payload_bytes(self) -> int:
        total = 0
        for b in self.layout.buckets:
            if self.levels[b.bid]:
                rows, _, _ = bucket_tile_shape(b.size)
                total += b.size + 4 * rows          # int8 codes + f32 scales
            else:
                total += 4 * b.size
        return total

    @property
    def f32_bytes(self) -> int:
        return 4 * sum(b.size for b in self.layout.buckets)

    def describe(self) -> str:
        mix: Dict[int, int] = {}
        for bt in self.bits:
            mix[bt] = mix.get(bt, 0) + 1
        bits = " ".join(f"{b}bx{n}" for b, n in sorted(mix.items()))
        return (f"weights[{len(self.layout.buckets)} buckets {bits}] "
                f"{self.payload_bytes / 2**20:.2f} MiB "
                f"({self.payload_bytes / max(self.f32_bytes, 1):.2%} of f32)")


def _resolve_plan(params, compression) -> Tuple[BucketLayout, CommPlan]:
    base = C.get(compression.compressor)
    if not (isinstance(base, C.StochasticQuant) and base.norm == "linf"):
        raise ServeError(
            f"weight quantization needs a linf StochasticQuant compressor "
            f"(int8-codes + scales payload); got "
            f"{compression.compressor!r}. l2/sign/topk compressors have no "
            f"weight-precision meaning here")
    layout = layout_for_params(
        params, bucket_bytes=int(compression.bucket_mb * 2**20))
    policy = compression.plan
    if policy == "none":
        policy = "uniform"
    plan = plan_comm(layout, compression.compressor, policy,
                     budget_bytes=int(compression.budget_mb * 2**20))
    return layout, plan


def _bucket_levels(plan: CommPlan, layout: BucketLayout) -> Tuple[int, ...]:
    """Per-bucket level count; 0 marks a raw (identity) bucket."""
    levels = []
    for b in layout.buckets:
        comp = C.get(plan.compressor_for(b.bid))
        if isinstance(comp, C.StochasticQuant) and comp.norm == "linf":
            levels.append(comp.levels)
        elif comp.name == "identity":
            levels.append(0)
        else:
            raise ServeError(
                f"weight plan assigned non-linf compressor {comp.name!r} "
                f"to bucket {b.bid}; only linf quant rungs and identity "
                f"are valid serving weight precisions")
    return tuple(levels)


def quantize_weights(params, compression, *, seed: int = 0):
    """One-shot load-time quantization.

    Returns (meta, payload): payload is a pytree of device arrays
    ({"b<bid>": {"codes", "scales"} | {"flat"}}) passed as the traced
    weights argument of the serving jits; meta is the static recipe
    `dequantize_weights` closes over.
    """
    leaves, treedef = jax.tree.flatten(params)
    layout, plan = _resolve_plan(params, compression)
    levels = _bucket_levels(plan, layout)
    bits = tuple(
        32 if lv == 0 else C.get(plan.compressor_for(b.bid)).bits
        for lv, b in zip(levels, layout.buckets))
    meta = WeightQuantMeta(
        layout=layout, plan=plan, treedef=treedef,
        leaf_structs=tuple(jax.ShapeDtypeStruct(l.shape, l.dtype)
                           for l in leaves),
        levels=levels, bits=bits)

    key = jax.random.key(seed)

    @jax.jit
    def encode(leaves):
        from repro.comm.buckets import pack
        flats = pack(layout, leaves)                     # f32, padded
        payload = {}
        for b in layout.buckets:
            flat = flats[b.bid]
            if levels[b.bid] == 0:
                payload[f"b{b.bid}"] = {"flat": flat}
                continue
            rand = jax.random.uniform(jax.random.fold_in(key, b.bid),
                                      flat.shape)
            codes, scales, _ = quantize_ef_flat(
                flat, jnp.zeros_like(flat), rand,
                levels=levels[b.bid])
            payload[f"b{b.bid}"] = {"codes": codes, "scales": scales}
        return payload

    return meta, encode(leaves)


def dequantize_weights(meta: WeightQuantMeta, payload):
    """Rebuild the parameter pytree from a payload (runs under jit — the
    dequant-on-read half of the contract)."""
    flats = []
    for b in meta.layout.buckets:
        entry = payload[f"b{b.bid}"]
        if meta.levels[b.bid] == 0:
            flats.append(entry["flat"])
            continue
        codes, scales = entry["codes"], entry["scales"]
        rows, cols, _ = bucket_tile_shape(b.size)
        deq = codes.astype(jnp.float32).reshape(rows, cols) * (
            scales[:, None] / meta.levels[b.bid])
        flats.append(deq.reshape(b.size))
    leaves = unpack_into(meta.layout, flats, list(meta.leaf_structs))
    return jax.tree.unflatten(meta.treedef, leaves)
