"""Gradient-exchange strategies: the parameter-server averaging of Algorithm 2
mapped onto TPU collectives (DESIGN.md §2).

All functions here run INSIDE a `jax.shard_map` that is manual over the
DQGAN worker axes (the paper's M machines) and auto over the tensor-model
axis. `p` is the per-worker message (η·g + e in the paper), and the return
value is (q̂, new_ef_state) where q̂ = (1/M) Σ_m Q(p^m) — exactly the
server-side average.

Strategies
----------
exact      : q̂ = pmean(p). No compression (CPOAdam baseline).
sim        : q̂ = pmean(Q(p)). Bit-exact paper semantics; float on the wire.
allgather  : int8 codes + scales all-gathered, dequantized, averaged.
             PS-uplink-faithful wire format; receive cost grows with M.
two_phase  : compressed "reduce-scatter + all-gather": quantize → all-to-all
             (int8) → chunk owner dequantizes + averages → re-quantize with
             owner-side EF → all-gather (int8). O(d·bits/8) per worker in
             BOTH phases — the TPU-native scalable scheme (beyond paper).

two_phase needs an axis of the tensor that is (a) divisible by the worker
count and (b) not sharded over a mesh axis (so the reshape is local). We
pick it statically from the tensor shape + PartitionSpec; tensors with no
such axis fall back to `sim` (recorded by `plan_for_tree`).

Bucketed fast path (repro.comm, DESIGN.md §3): when DQConfig.comm_plan is
a planner policy, core.dqgan packs unsharded leaves into flat buckets
whose padded length is always divisible by the worker count, and calls
`exchange_leaf` with `plan_bucket` plans (chunk axis 0) — one collective
per bucket instead of one per tensor, and no two_phase→sim fallbacks.
Wire cost per strategy is accounted by comm.ledger.CommLedger.

Split-phase contract (DESIGN.md §13): every strategy is expressed as
``start_exchange(...) -> ExchangeHandle`` followed by
``finish_exchange(handle) -> (q̂, new_ef_state)``. The *start* phase emits
everything up to and including the wire collectives (compress, EF update,
pmean / all-gather / all-to-all); the *finish* phase emits only local
post-processing (decompress, mean, reshape). Starting round-*s*'s handle
before the round-*s* field compute and finishing it at consumption time
is what lets XLA's latency-hiding scheduler overlap wire time with
generator/discriminator compute for `Schedule.delayed(τ)`. The blocking
`exchange_leaf` is a deprecation shim equal to start+immediate-finish,
so every_step/local_k graphs are bit-identical to the pre-split API.

The typed front-end for choosing among these is
`repro.strategy.ExchangePlan` (DESIGN.md §9): `ExchangePlan.leaf_plans`
→ `plan_for_tree`, `ExchangePlan.bucket_plan` → `plan_bucket`,
`ExchangePlan.start/finish` → `start_exchange`/`finish_exchange`, with
the kind validated against `STRATEGIES` at construction.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import compressors as C
from .error_feedback import compress_with_ef

STRATEGIES = ("exact", "sim", "allgather", "two_phase")


# --------------------------------------------------------------------------- #
# static planning
# --------------------------------------------------------------------------- #
def pick_chunk_axis(shape, spec: Optional[P], n_workers: int) -> Optional[int]:
    """Largest axis divisible by n_workers whose PartitionSpec entry is None."""
    best = None
    for ax, size in enumerate(shape):
        sharded = spec is not None and ax < len(spec) and spec[ax] is not None
        if sharded or size % n_workers:
            continue
        if best is None or size > shape[best]:
            best = ax
    return best


def plan_leaf(strategy: str, shape, spec, n_workers: int) -> dict:
    """Resolve the effective strategy + chunk axis for one tensor."""
    if strategy == "two_phase":
        ax = pick_chunk_axis(shape, spec, n_workers)
        if ax is None:
            return {"strategy": "sim", "chunk_axis": None, "fallback": True}
        return {"strategy": "two_phase", "chunk_axis": ax, "fallback": False}
    return {"strategy": strategy, "chunk_axis": None, "fallback": False}


def plan_bucket(strategy: str, size: int, n_workers: int) -> dict:
    """Plan for a flat comm bucket. Bucket sizes are padded to a multiple
    of n_workers (buckets.build_layout), so two_phase always chunks on
    axis 0 and never falls back."""
    if strategy == "two_phase":
        assert size % max(n_workers, 1) == 0, (size, n_workers)
        return {"strategy": "two_phase", "chunk_axis": 0, "fallback": False}
    return {"strategy": strategy, "chunk_axis": None, "fallback": False}


def plan_has_owner_ef(plan: dict) -> bool:
    """True when `plan` carries owner-side (e2) error feedback — today
    only two_phase. The one place that knowledge lives: callers
    (core.dqgan, strategy.ExchangePlan.owner_ef) ask this instead of
    string-matching on the strategy name."""
    return plan["strategy"] == "two_phase"


def plan_for_tree(strategy, shapes_tree, specs_tree, n_workers):
    return jax.tree.map(
        lambda sh, sp: plan_leaf(strategy, sh, sp, n_workers),
        shapes_tree,
        specs_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(i, int) for i in x),
    )


# --------------------------------------------------------------------------- #
# EF state
# --------------------------------------------------------------------------- #
def ef_state_zeros(plan: dict, shape, dtype, n_workers: int, use_ef: bool):
    """Per-leaf EF state. e1 = worker-side error (full shape); e2 = chunk-owner
    error for two_phase (1/W of the tensor, sharded over workers naturally)."""
    state = {}
    if use_ef:
        state["e1"] = jnp.zeros(shape, dtype)
    if plan["strategy"] == "two_phase":
        ax = plan["chunk_axis"]
        chunk_shape = list(shape)
        chunk_shape[ax] //= n_workers
        state["e2"] = jnp.zeros(tuple(chunk_shape), dtype)
    return state


# --------------------------------------------------------------------------- #
# collectives
# --------------------------------------------------------------------------- #
def _mean_axes(x, axes):
    return jax.lax.pmean(x, axes)


def _all_to_all(c, axes):
    """all_to_all with leading source-worker dim (split/concat axis 0)."""
    return jax.lax.all_to_all(c, axes, split_axis=0, concat_axis=0,
                              tiled=False)


# --------------------------------------------------------------------------- #
# split-phase API
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class ExchangeHandle:
    """In-flight exchange for one tensor (DESIGN.md §13).

    Produced by `start_exchange` after the wire collectives have been
    *issued* into the trace; `finish_exchange` emits the local
    post-processing and returns (q̂, new_ef_state). The handle is a
    trace-time object (it closes over traced arrays), valid only within
    the jitted step that created it — it is NOT a pytree and must not
    cross a `jit` boundary or be stored in carried state. For
    `delayed(τ)` the pending ring keeps carrying the *message* arrays;
    the handle's lifetime is one trace: started before the round's field
    compute, finished when the τ-stale result is consumed.
    """
    strategy: str
    _finish: Callable[[], Tuple[Any, dict]]

    def finish(self):
        return self._finish()


def _resolved(strategy, q, new_state) -> ExchangeHandle:
    return ExchangeHandle(strategy, lambda: (q, new_state))


def start_exchange(
    compressor: C.Compressor,
    plan: dict,
    p,
    ef_state: dict,
    key,
    axes: Tuple[str, ...],
    n_workers: int,
    use_ef: bool,
) -> ExchangeHandle:
    """Issue the wire collectives for one tensor; return a handle whose
    `finish_exchange` yields (q̂, new_ef_state). Runs under
    shard_map(axes).

    Split points per strategy (start | finish):
      exact     : pmean(p)                              | identity
      sim       : compress+EF, pmean(p̂)                 | identity
      allgather : compress+EF, all_gather(codes)        | decompress+mean
      two_phase : phase 1+2 through all_gather(codes2)  | decompress+unchunk
    EF-state updates are start-side (they depend only on local compress
    results), so staleness semantics are unchanged by the split.
    """
    strategy = plan["strategy"]
    new_state = dict(ef_state)

    if strategy == "exact":
        return _resolved(strategy, _mean_axes(p, axes), new_state)

    if strategy == "sim":
        e1 = ef_state.get("e1", jnp.zeros_like(p))
        payload, p_hat, e_new = compress_with_ef(compressor, p, e1, key, use_ef=use_ef)
        del payload
        if use_ef:
            new_state["e1"] = e_new
        return _resolved(strategy, _mean_axes(p_hat, axes), new_state)

    if strategy == "allgather":
        e1 = ef_state.get("e1", jnp.zeros_like(p))
        payload, p_hat, e_new = compress_with_ef(compressor, p, e1, key, use_ef=use_ef)
        if use_ef:
            new_state["e1"] = e_new
        gathered = jax.tree.map(
            lambda x: jax.lax.all_gather(x, axes), payload)

        def _finish_allgather():
            deq = jax.vmap(
                lambda pl: compressor.decompress(pl, p.shape, jnp.float32)
            )(gathered)
            return jnp.mean(deq, axis=0).astype(p.dtype), new_state

        return ExchangeHandle(strategy, _finish_allgather)

    if strategy == "two_phase":
        return _start_two_phase(compressor, plan, p, ef_state, new_state, key,
                                axes, n_workers, use_ef)

    raise ValueError(f"unknown strategy {strategy!r}")


def finish_exchange(handle: ExchangeHandle):
    """Emit the local post-processing of a started exchange and return
    (q̂, new_ef_state)."""
    return handle.finish()


def exchange_leaf(
    compressor: C.Compressor,
    plan: dict,
    p,
    ef_state: dict,
    key,
    axes: Tuple[str, ...],
    n_workers: int,
    use_ef: bool,
):
    """Blocking shim: start + immediate finish (deprecated spelling).

    Kept so external callers of the pre-split API keep working and so
    the overlap=False lowering is bit-identical to the historical graphs
    (same per-leaf op emission order). New code should go through
    `ExchangePlan.start`/`ExchangePlan.finish` (repro.strategy) or the
    module-level `start_exchange`/`finish_exchange` pair.
    """
    return finish_exchange(start_exchange(
        compressor, plan, p, ef_state, key, axes, n_workers, use_ef))


def _start_two_phase(compressor, plan, p, ef_state, new_state, key, axes, W,
                     use_ef) -> ExchangeHandle:
    ax = plan["chunk_axis"]
    orig_shape = p.shape
    # ---- phase 1: worker-side compress + all-to-all ------------------------ #
    e1 = ef_state.get("e1", jnp.zeros_like(p))
    m = p + e1.astype(p.dtype) if use_ef else p
    # split the chunk axis: (..., ax, ...) -> (W, ..., ax/W, ...)
    x = jnp.moveaxis(m, ax, 0).reshape((W, orig_shape[ax] // W) + _rest(orig_shape, ax))
    keys = jax.random.split(key, W + 1)
    payload = jax.vmap(compressor.compress)(x, keys[:W])
    x_hat = jax.vmap(lambda pl: compressor.decompress(pl, x.shape[1:], x.dtype))(payload)
    if use_ef:
        e_new = (x - x_hat).reshape((orig_shape[ax],) + _rest(orig_shape, ax))
        new_state["e1"] = jnp.moveaxis(e_new, 0, ax).astype(e1.dtype)
    # all-to-all: leading dim becomes the source-worker index, int8 on the wire
    moved = jax.tree.map(lambda c: _all_to_all(c, axes), payload)
    contrib = jax.vmap(
        lambda pl: compressor.decompress(pl, x.shape[1:], jnp.float32)
    )(moved)
    chunk_mean = jnp.mean(contrib, axis=0)  # this worker's chunk of q̂
    # ---- phase 2: owner-side compress (+ owner EF) + all-gather ------------ #
    e2 = ef_state["e2"].reshape(chunk_mean.shape)
    payload2, chunk_hat, e2_new = compress_with_ef(
        compressor, chunk_mean, e2, keys[W], use_ef=True
    )
    del chunk_hat
    new_state["e2"] = e2_new.reshape(ef_state["e2"].shape).astype(ef_state["e2"].dtype)
    gathered = jax.tree.map(lambda c: jax.lax.all_gather(c, axes), payload2)

    def _finish_two_phase():
        chunks = jax.vmap(
            lambda pl: compressor.decompress(pl, chunk_mean.shape, jnp.float32)
        )(gathered)
        q = jnp.moveaxis(
            chunks.reshape((orig_shape[ax],) + _rest(orig_shape, ax)), 0, ax
        )
        return q.astype(p.dtype), new_state

    return ExchangeHandle("two_phase", _finish_two_phase)


def _rest(shape, ax):
    return tuple(s for i, s in enumerate(shape) if i != ax)


# --------------------------------------------------------------------------- #
# fsdp split-phase primitives (DESIGN.md §15)
# --------------------------------------------------------------------------- #
def start_reduce_scatter(
    compressor: C.Compressor,
    kind: str,
    p,
    ef_state: dict,
    key,
    axes: Tuple[str, ...],
    n_workers: int,
    use_ef: bool,
) -> ExchangeHandle:
    """The fsdp gradient leg: (compressed) reduce-scatter of one flat,
    worker-divisible bucket (DESIGN.md §15.2). ``p`` is (d,) with
    d % W == 0; the handle finishes to (q_shard, new_ef_state), q_shard
    being this worker's (d/W,) chunk of the mean message.

    Split points (start | finish):
      exact     : psum_scatter(p)/W                        | identity
      two_phase : compress+EF per chunk, all_to_all(int8)  | dequant+mean

    The compressed form is exactly phase 1 of `two_phase` — worker-side
    e1 error feedback, int8 on the wire — without phase 2's owner
    requantization: the shard owner consumes q_shard directly (optimizer
    update), and what returns to the replicas is the separately
    compressed moments leg (`start_all_gather_shard`)."""
    W = max(n_workers, 1)
    new_state = dict(ef_state)
    if W <= 1 or not axes:
        # single-worker degenerate: the shard IS the bucket; keep the
        # compressor roundtrip so W=1 matches the W>1 math per worker
        if kind == "exact":
            return _resolved(kind, p, new_state)
        e1 = ef_state.get("e1", jnp.zeros_like(p))
        payload, p_hat, e_new = compress_with_ef(
            compressor, p, e1, key, use_ef=use_ef)
        del payload
        if use_ef:
            new_state["e1"] = e_new.astype(e1.dtype)
        return _resolved(kind, p_hat.astype(p.dtype), new_state)
    if kind == "exact":
        # reduce-scatter: worker w receives sum_m p_m[w·d/W:(w+1)·d/W]
        q = jax.lax.psum_scatter(p, axes, scatter_dimension=0,
                                 tiled=True) / W
        return _resolved(kind, q.astype(p.dtype), new_state)
    if kind != "two_phase":
        raise ValueError(
            f"fsdp reduce-scatter: kind must be 'exact' or 'two_phase', "
            f"got {kind!r}")
    chunk = p.shape[0] // W
    e1 = ef_state.get("e1", jnp.zeros_like(p))
    m = p + e1.astype(p.dtype) if use_ef else p
    x = m.reshape(W, chunk)
    keys = jax.random.split(key, W)
    payload = jax.vmap(compressor.compress)(x, keys)
    if use_ef:
        x_hat = jax.vmap(
            lambda pl: compressor.decompress(pl, (chunk,), x.dtype)
        )(payload)
        new_state["e1"] = (x - x_hat).reshape(-1).astype(e1.dtype)
    # int8 codes on the wire; leading dim becomes the source-worker index
    moved = jax.tree.map(lambda c: _all_to_all(c, axes), payload)

    def _finish_rs():
        contrib = jax.vmap(
            lambda pl: compressor.decompress(pl, (chunk,), jnp.float32)
        )(moved)
        return jnp.mean(contrib, axis=0).astype(p.dtype), new_state

    return ExchangeHandle(kind, _finish_rs)


def start_all_gather_shard(
    compressor: C.Compressor,
    shard,
    ag_ef,
    key,
    axes: Tuple[str, ...],
    n_workers: int,
    use_ef: bool,
) -> ExchangeHandle:
    """The fsdp return leg: (compressed) all-gather of one owner shard —
    the quantized optimizer-state/parameter exchange of arXiv 2004.14180
    (DESIGN.md §15.3). The owner quantizes (shard + residual) and keeps
    e_new = (shard + e) − Q(shard + e); every worker decompresses the same
    W payloads, so the gathered flat bucket is identical on all replicas.
    Finishes to (full (W·chunk,) flat bucket, new owner residual)."""
    W = max(n_workers, 1)
    payload, c_hat, e_new = compress_with_ef(
        compressor, shard, ag_ef, key, use_ef=use_ef)
    new_ef = e_new if use_ef else ag_ef
    if W <= 1 or not axes:
        def _finish_local():
            return c_hat.astype(shard.dtype), new_ef
        return ExchangeHandle("allgather_shard", _finish_local)
    del c_hat
    gathered = jax.tree.map(lambda c: jax.lax.all_gather(c, axes),
                            payload)

    def _finish_ag():
        chunks = jax.vmap(
            lambda pl: compressor.decompress(pl, shard.shape, jnp.float32)
        )(gathered)
        return chunks.reshape(-1).astype(shard.dtype), new_ef

    return ExchangeHandle("allgather_shard", _finish_ag)


# --------------------------------------------------------------------------- #
# modeled wire bytes (for the speedup benchmark + roofline cross-check)
# --------------------------------------------------------------------------- #
def transport_factor(n_workers: int) -> float:
    """Ring-transport multiplier 2·(W−1)/W: per-worker wire bytes of a
    ring all-reduce (reduce-scatter + all-gather) relative to payload
    size. The single spelling shared by `modeled_wire_bytes`, the
    strategy component (`ExchangePlan.transport_factor`), and the
    compiled-HLO byte gap (`obs.hlo.byte_gap`)."""
    return 2 * (n_workers - 1) / max(n_workers, 1)


def modeled_wire_bytes(strategy, compressor, shape, n_workers):
    """Per-worker bytes moved for one tensor, by strategy (send+receive)."""
    d = math.prod(shape)
    full = 4 * d
    cb = compressor.wire_bytes(shape, n_workers)
    if strategy == "exact" or strategy == "sim":
        # ring all-reduce: 2·(W-1)/W · d · 4  ≈ 8d
        return transport_factor(n_workers) * full
    if strategy == "allgather":
        return cb + (n_workers - 1) * cb  # send own + receive all others
    if strategy == "two_phase":
        return transport_factor(n_workers) * cb  # A2A + AG, compressed
    raise ValueError(strategy)


def modeled_fsdp_wire_bytes(kind, compressor, moment_compressor, shape,
                            n_workers):
    """Per-worker bytes of one fsdp round for one bucket: the gradient
    reduce-scatter ((W−1)/W · payload sent) plus the moments/param
    all-gather ((W−1)/W · payload). With kind='exact' and identity
    moments this equals `modeled_wire_bytes('exact', ...)` — fsdp's
    RS+AG *is* the ring all-reduce, split around the optimizer."""
    d = math.prod(shape)
    W = max(n_workers, 1)
    f = (W - 1) / W
    rs = 4 * d if kind == "exact" else compressor.wire_bytes(shape, W)
    ag = moment_compressor.wire_bytes(shape, W)
    return f * (rs + ag)
