"""Sequential (batch-1) generation — the engine's correctness oracle and
throughput baseline.

Runs the same floor-bucket prefill + tail-decode schedule and the same
`sample_token` draw as the continuous-batching engine, over the model's
*dense* decode cache sized once to `ServeConfig.max_context` (the same
gathered length the paged decode reduces over, so engine-vs-baseline
token equality is bit-exact, not approximate). Compiled functions are
hoisted and cached per prompt bucket — this class is also the fix for
the old launcher's per-call re-jit (`trace` counters pin it in tests).
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import numpy as np

from repro.models import model as lm

from .engine import sample_token
from .kv_cache import (
    ServeConfig,
    check_model_servable,
    dense_cache_len,
    plan_request,
)
from .quantized_weights import dequantize_weights, quantize_weights


class SequentialGenerator:
    """One request at a time over a dense cache; same tokens as Engine."""

    def __init__(self, cfg, serve_cfg: ServeConfig, params, *,
                 compression=None, seed: int = 0):
        check_model_servable(cfg)
        self.cfg = cfg
        self.scfg = serve_cfg
        self.weight_meta = None
        if compression is not None:
            self.weight_meta, self._weights = quantize_weights(
                params, compression, seed=seed)
        else:
            self._weights = params
        self._base_key = jax.random.key(seed)
        self.decode_traces: List[int] = []
        self.prefill_traces: Dict[int, int] = {}
        self.steps = 0
        self._decode = jax.jit(self._decode_impl)
        self._prefills: Dict[int, object] = {}

    def _dequant(self, weights):
        if self.weight_meta is None:
            return weights
        return dequantize_weights(self.weight_meta, weights)

    def _decode_impl(self, weights, tokens, caches):
        self.decode_traces.append(1)
        params = self._dequant(weights)
        return lm.decode_step(params, self.cfg, tokens, caches)

    def _prefill_for(self, bucket: int):
        if bucket not in self._prefills:
            max_len = dense_cache_len(self.scfg)

            def fn(weights, tokens):
                self.prefill_traces[bucket] = \
                    self.prefill_traces.get(bucket, 0) + 1
                params = self._dequant(weights)
                return lm.prefill(params, self.cfg, tokens, None,
                                  max_len=max_len)
            self._prefills[bucket] = jax.jit(fn)
        return self._prefills[bucket]

    def generate(self, prompt: List[int], max_new: int, *, rid: int = 0,
                 temperature: float = 0.0,
                 stop_token: Optional[int] = None) -> List[int]:
        bucket, _ = plan_request(len(prompt), max_new, self.scfg)
        out: List[int] = []

        if bucket > 0:
            logits, caches = self._prefill_for(bucket)(
                self._weights, np.asarray([prompt[:bucket]], np.int32))
        else:
            logits = None
            caches = lm.init_cache(self.cfg, 1, dense_cache_len(self.scfg))
        to_feed = list(prompt[bucket:])

        last = 0
        if not to_feed:                      # bucket == len(prompt)
            tok = sample_token(logits[0], temperature, rid, 0,
                               self._base_key)
            out.append(tok)
            if max_new == 1 or tok == stop_token:
                return out
            last = tok

        while True:
            inp = to_feed[0] if to_feed else last
            logits, caches = self._decode(
                self._weights, np.asarray([[inp]], np.int32), caches)
            self.steps += 1
            if to_feed:
                to_feed.pop(0)
                if to_feed:
                    continue                 # still consuming the prompt
            tok = sample_token(logits[0], temperature, rid, len(out),
                               self._base_key)
            out.append(tok)
            if len(out) >= max_new or tok == stop_token:
                return out
            last = tok

    def stats(self) -> dict:
        return {
            "decode_traces": len(self.decode_traces),
            "prefill_traces": dict(self.prefill_traces),
            "steps": self.steps,
            "weights": (self.weight_meta.describe()
                        if self.weight_meta else "f32"),
        }
