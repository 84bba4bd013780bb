"""Continuous-batching serving engine (DESIGN.md §14).

One fixed-shape jitted decode step serves `max_batch` slots; requests
join and leave at decode-step granularity without retracing (the trace
counters are part of the public stats, and CI asserts exactly one decode
trace across churn). Prefill runs per request at a *floor* bucket — the
largest configured bucket that fits inside the prompt — and the
remaining prompt tail is fed through the shared decode step one token
per step (chunked prefill). No pad token ever enters the model, which is
what keeps recurrent mixers (RG-LRU / SSD) exact: their prefill state is
the state of the true prompt, not of a right-padded one.

Token accounting per request (prompt length P, floor bucket F ≤ P,
max_new G): prefill covers positions 0..F-1; decode steps consume
prompt[F..P-1] then the sampled tokens, writing positions F..P+G-2; the
step that consumes prompt[P-1] (or the prefill itself when F == P)
yields generated token 0, so a request costs (P-F) + (G-1) decode steps
and P+G-1 KV positions. The sequential baseline (baseline.py) runs the
identical graphs at batch 1, which is what makes engine-vs-sequential
token equality exact rather than approximate.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers
from repro.models import model as lm

from .kv_cache import (
    BlockAllocator,
    ServeConfig,
    ServeError,
    check_model_servable,
    init_paged_cache,
    plan_request,
)
from .quantized_weights import dequantize_weights, quantize_weights


# --------------------------------------------------------------------------- #
# requests
# --------------------------------------------------------------------------- #
@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int
    temperature: float = 0.0
    stop_token: Optional[int] = None
    arrival: float = 0.0            # virtual seconds (load generator clock)


def sample_token(logits_row, temperature: float, rid: int, index: int,
                 base_key) -> int:
    """Shared by the engine and the sequential baseline so both draw the
    same token from the same logits: greedy argmax at temperature <= 0,
    else categorical under a (seed, rid, token-index) key — independent of
    scheduling order, so continuous batching cannot perturb sampling."""
    if temperature <= 0.0:
        return int(jnp.argmax(logits_row))
    k = jax.random.fold_in(jax.random.fold_in(base_key, rid), index)
    return int(jax.random.categorical(
        k, logits_row.astype(jnp.float32) / temperature))


# --------------------------------------------------------------------------- #
# cache tree surgery (all shapes static per bucket — each walk jits once)
# --------------------------------------------------------------------------- #
def _walk(paged, pre, attn_fn, state_fn, stacked):
    """Parallel walk of the paged cache (template) and a prefill cache;
    attn leaves are dicts with a "table", everything else recurses down to
    recurrent-state arrays."""
    if isinstance(paged, dict):
        if "table" in paged:
            return attn_fn(paged, pre, stacked)
        return {k: _walk(paged[k], None if pre is None else pre[k],
                         attn_fn, state_fn, stacked) for k in paged}
    if isinstance(paged, list):
        return [_walk(a, None if pre is None else b, attn_fn, state_fn,
                      stacked)
                for a, b in zip(paged, pre if pre is not None else paged)]
    return state_fn(paged, pre, stacked)


def _map_cache(cache, attn_fn, state_fn, pre=None):
    out = {"scan": None, "tail": []}
    if cache.get("scan") is not None:
        out["scan"] = _walk(cache["scan"],
                            None if pre is None else pre["scan"],
                            attn_fn, state_fn, stacked=True)
    for i, leaf in enumerate(cache.get("tail", [])):
        out["tail"].append(_walk(
            leaf, None if pre is None else pre["tail"][i],
            attn_fn, state_fn, stacked=False))
    return out


def _park_tables(cache, active):
    """Point every inactive slot's table row at the scratch block, so the
    fixed-shape decode's writes for vacated slots can never land in a
    block the allocator has handed to a live request."""
    def attn(leaf, _, stacked):
        mask = active[None, :, None] if stacked else active[:, None]
        return dict(leaf, table=jnp.where(mask, leaf["table"], 0))

    return _map_cache(cache, attn, lambda s, _, st: s)


def _insert_prefill(cache, pre, slot, block_ids, row, block_size):
    """Scatter a batch-1 prefill cache into `slot`: K/V into the request's
    pool blocks (whole blocks — buckets are block-aligned), the block map
    into the slot's table row, recurrent states into the slot's lane."""
    nb = block_ids.shape[0]

    def attn(leaf, p, stacked):
        k, v = p["k"], p["v"]
        if stacked:
            ns = k.shape[0]
            kb = k[:, 0].reshape(ns, nb, block_size, *k.shape[3:])
            vb = v[:, 0].reshape(ns, nb, block_size, *v.shape[3:])
            return {"k": leaf["k"].at[:, block_ids].set(kb),
                    "v": leaf["v"].at[:, block_ids].set(vb),
                    "table": leaf["table"].at[:, slot].set(row)}
        kb = k[0].reshape(nb, block_size, *k.shape[2:])
        vb = v[0].reshape(nb, block_size, *v.shape[2:])
        return {"k": leaf["k"].at[block_ids].set(kb),
                "v": leaf["v"].at[block_ids].set(vb),
                "table": leaf["table"].at[slot].set(row)}

    def state(leaf, p, stacked):
        if stacked:
            return leaf.at[:, slot].set(p[:, 0])
        return leaf.at[slot].set(p[0])

    return _map_cache(cache, attn, state, pre=pre)


def _claim_slot(cache, slot, row):
    """Admission without prefill (prompt shorter than every bucket): write
    the block map and zero the slot's recurrent state lanes (the previous
    occupant's state must not leak into a fresh request)."""
    def attn(leaf, _, stacked):
        if stacked:
            return dict(leaf, table=leaf["table"].at[:, slot].set(row))
        return dict(leaf, table=leaf["table"].at[slot].set(row))

    def state(leaf, _, stacked):
        if stacked:
            return leaf.at[:, slot].set(jnp.zeros_like(leaf[:, slot]))
        return leaf.at[slot].set(jnp.zeros_like(leaf[slot]))

    return _map_cache(cache, attn, state)


# --------------------------------------------------------------------------- #
# the engine
# --------------------------------------------------------------------------- #
@dataclass
class _Slot:
    rid: int
    to_feed: List[int]              # prompt tokens not yet consumed
    blocks: List[int]
    max_new: int
    temperature: float
    stop_token: Optional[int]
    last_tok: int = 0
    emitted: int = 0


class Engine:
    """Continuous-batching decode over a paged KV cache.

    submit() enqueues; step() admits whatever fits (FIFO — the head blocks
    the queue until slots AND blocks are free, a deliberate no-starvation
    policy), runs ONE fixed-shape decode for all live slots, samples, and
    releases finished requests' blocks back to the free list. All compiled
    functions are built once: `decode_traces` must stay at 1 forever.
    """

    def __init__(self, cfg, serve_cfg: ServeConfig, params, *,
                 compression=None, seed: int = 0, attn_impl: str = "gather"):
        check_model_servable(cfg)
        if attn_impl not in ("gather", "pallas"):
            raise ServeError(f"attn_impl must be gather|pallas, "
                             f"got {attn_impl!r}")
        self.cfg = cfg
        self.scfg = serve_cfg
        self.attn_impl = attn_impl
        self.weight_meta = None
        if compression is not None:
            self.weight_meta, self._weights = quantize_weights(
                params, compression, seed=seed)
        else:
            self._weights = params

        self.cache = init_paged_cache(cfg, serve_cfg)
        self.alloc = BlockAllocator(serve_cfg.num_blocks)
        self.slots: List[Optional[_Slot]] = [None] * serve_cfg.max_batch
        self._lengths = [0] * serve_cfg.max_batch
        self.queue: deque = deque()
        self.outputs: Dict[int, List[int]] = {}
        self.completed = set()
        self._base_key = jax.random.key(seed)

        self.decode_traces: List[int] = []
        self.prefill_traces: Dict[int, int] = {}
        self.steps = 0
        self.peak_occupancy = 0.0

        self._decode = jax.jit(self._decode_impl)
        self._prefills: Dict[int, object] = {}
        self._inserts: Dict[int, object] = {}
        self._claim = jax.jit(self._claim_impl)

    # -- compiled pieces --------------------------------------------------- #
    def _dequant(self, weights):
        if self.weight_meta is None:
            return weights
        return dequantize_weights(self.weight_meta, weights)

    def _decode_impl(self, weights, cache, tokens, lengths, active):
        self.decode_traces.append(1)
        prev = layers.set_paged_attn_impl(self.attn_impl)
        try:
            params = self._dequant(weights)
            cache = _park_tables(cache, active)
            logits, cache = lm.decode_step_paged(params, self.cfg, tokens,
                                                 cache, lengths)
        finally:
            layers.set_paged_attn_impl(prev)
        return logits, cache

    def _claim_impl(self, cache, slot, row):
        return _claim_slot(cache, slot, row)

    def _prefill_for(self, bucket: int):
        if bucket not in self._prefills:
            def fn(weights, tokens):
                self.prefill_traces[bucket] = \
                    self.prefill_traces.get(bucket, 0) + 1
                params = self._dequant(weights)
                return lm.prefill(params, self.cfg, tokens)
            self._prefills[bucket] = jax.jit(fn)
        return self._prefills[bucket]

    def _insert_for(self, bucket: int):
        if bucket not in self._inserts:
            bs = self.scfg.block_size
            self._inserts[bucket] = jax.jit(
                lambda cache, pre, slot, block_ids, row:
                _insert_prefill(cache, pre, slot, block_ids, row, bs))
        return self._inserts[bucket]

    # -- request lifecycle ------------------------------------------------- #
    def submit(self, req: Request) -> None:
        if req.rid in self.outputs:
            raise ServeError(f"duplicate request id {req.rid}")
        bucket, n_blocks = plan_request(len(req.prompt), req.max_new,
                                        self.scfg)
        self.outputs[req.rid] = []
        self.queue.append((req, bucket, n_blocks))

    def _sample(self, logits_row, s: _Slot) -> int:
        return sample_token(logits_row, s.temperature, s.rid, s.emitted,
                            self._base_key)

    def _finish(self, idx: int, s: _Slot) -> None:
        self.alloc.free(s.blocks)
        self.slots[idx] = None
        self._lengths[idx] = 0
        self.completed.add(s.rid)

    def _try_admit(self) -> None:
        while self.queue:
            req, bucket, n_blocks = self.queue[0]
            P = len(req.prompt)
            if bucket == P and req.max_new == 1:
                # generated token 0 falls out of the prefill logits: the
                # request completes without ever occupying a decode slot
                self.queue.popleft()
                logits, _ = self._prefill_for(bucket)(
                    self._weights,
                    np.asarray([req.prompt], np.int32))
                tok = sample_token(logits[0], req.temperature, req.rid, 0,
                                   self._base_key)
                self.outputs[req.rid].append(tok)
                self.completed.add(req.rid)
                continue
            idx = next((i for i, s in enumerate(self.slots) if s is None),
                       None)
            if idx is None or n_blocks > self.alloc.free_blocks:
                return                        # FIFO: head blocks the queue
            self.queue.popleft()
            self._admit(req, bucket, n_blocks, idx)

    def _admit(self, req: Request, bucket: int, n_blocks: int,
               idx: int) -> None:
        blocks = self.alloc.alloc(n_blocks)
        row = np.zeros(self.scfg.max_blocks_per_seq, np.int32)
        row[:n_blocks] = blocks
        s = _Slot(rid=req.rid, to_feed=list(req.prompt[bucket:]),
                  blocks=blocks, max_new=req.max_new,
                  temperature=req.temperature, stop_token=req.stop_token)
        if bucket > 0:
            logits, pre = self._prefill_for(bucket)(
                self._weights, np.asarray([req.prompt[:bucket]], np.int32))
            nb_prefill = bucket // self.scfg.block_size
            self.cache = self._insert_for(bucket)(
                self.cache, pre, np.int32(idx),
                np.asarray(blocks[:nb_prefill], np.int32), row)
            if not s.to_feed:               # bucket == P: token 0 is here
                tok = self._sample(logits[0], s)
                self.outputs[s.rid].append(tok)
                s.emitted = 1
                if tok == s.stop_token:     # max_new == 1 handled pre-slot
                    self.alloc.free(blocks)
                    self.completed.add(s.rid)
                    return
                s.last_tok = tok
        else:
            self.cache = self._claim(self.cache, np.int32(idx), row)
        self._lengths[idx] = bucket
        self.slots[idx] = s
        self.peak_occupancy = max(self.peak_occupancy,
                                  self.alloc.occupancy())

    def step(self) -> bool:
        """Admit + one batched decode + sample/release. Returns False when
        there was nothing to do (no live slots after admission)."""
        self._try_admit()
        live = [i for i, s in enumerate(self.slots) if s is not None]
        if not live:
            return False
        B = self.scfg.max_batch
        tokens = np.zeros((B, 1), np.int32)
        active = np.zeros((B,), bool)
        for i in live:
            s = self.slots[i]
            tokens[i, 0] = s.to_feed[0] if s.to_feed else s.last_tok
            active[i] = True
        lengths = np.asarray(self._lengths, np.int32)
        logits, self.cache = self._decode(self._weights, self.cache, tokens,
                                          lengths, active)
        self.steps += 1
        for i in live:
            s = self.slots[i]
            self._lengths[i] += 1
            if s.to_feed:
                s.to_feed.pop(0)
                if s.to_feed:
                    continue                 # still consuming the prompt
            tok = self._sample(logits[i], s)
            self.outputs[s.rid].append(tok)
            s.emitted += 1
            if s.emitted >= s.max_new or tok == s.stop_token:
                self._finish(i, s)
            else:
                s.last_tok = tok
        return True

    # -- driving ----------------------------------------------------------- #
    @property
    def idle(self) -> bool:
        return not self.queue and all(s is None for s in self.slots)

    def run(self, requests) -> Dict[int, List[int]]:
        """Drain a batch of requests (arrival times ignored — closed loop);
        returns {rid: generated tokens}."""
        for r in sorted(requests, key=lambda r: r.arrival):
            self.submit(r)
        while not self.idle:
            if not self.step() and self.queue:
                raise ServeError(
                    "admission deadlock: queue non-empty but nothing "
                    "admitted with all slots free")
        return self.outputs

    def stats(self) -> dict:
        live_tokens = sum(self._lengths)
        return {
            "decode_traces": len(self.decode_traces),
            "prefill_traces": dict(self.prefill_traces),
            "steps": self.steps,
            "occupancy": self.alloc.occupancy(),
            "peak_occupancy": self.peak_occupancy,
            "live_tokens": live_tokens,
            "weights": (self.weight_meta.describe()
                        if self.weight_meta else "f32"),
        }
