"""Model registry: bind a ModelConfig/GANConfig to a uniform functional
bundle used by training, serving, the dry-run, and the tests."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from . import biggan
from . import gan as gan_lib
from . import model as lm


@dataclass(frozen=True)
class ModelBundle:
    cfg: Any
    init: Callable                  # (key, max_seq) -> params
    # (params, batch, rng) -> (grads, metrics); a stateful field (BigGAN's
    # spectral-norm vectors) takes and returns its state too and carries
    # `init_state(params)`, see core.dqgan.DQGAN
    field_fn: Callable
    loss_fn: Optional[Callable]     # (params, batch) -> (loss, metrics)
    prefill: Optional[Callable]     # (params, tokens[, enc]) -> (logits, cache)
    decode_step: Optional[Callable]
    init_cache: Optional[Callable]
    # (params, tokens (B,1), paged_caches, lengths (B,)) -> (logits, caches);
    # the repro.serve engine's per-row-position decode (None for GANs).
    decode_paged: Optional[Callable] = None


def build(cfg) -> ModelBundle:
    if isinstance(cfg, (gan_lib.GANConfig, biggan.BigGANConfig)):
        return ModelBundle(
            cfg=cfg,
            init=lambda key, max_seq=0: gan_lib.init(key, cfg),
            field_fn=gan_lib.gan_field_fn(cfg),
            loss_fn=None,
            prefill=None,
            decode_step=None,
            init_cache=None,
        )
    assert isinstance(cfg, ModelConfig), cfg

    def loss_fn(params, batch):
        return lm.loss_fn(params, cfg, batch)

    def field_fn(params, batch, rng):
        del rng
        (_, metrics), grads = jax.value_and_grad(
            lambda p: lm.loss_fn(p, cfg, batch), has_aux=True
        )(params)
        return grads, metrics

    return ModelBundle(
        cfg=cfg,
        init=lambda key, max_seq=0: lm.init(key, cfg, max_seq),
        field_fn=field_fn,
        loss_fn=loss_fn,
        prefill=lambda params, tokens, enc=None, max_len=0: lm.prefill(
            params, cfg, tokens, enc, max_len=max_len),
        decode_step=lambda params, tokens, caches: lm.decode_step(
            params, cfg, tokens, caches),
        init_cache=lambda batch, seq, dtype=None: lm.init_cache(
            cfg, batch, seq, dtype),
        decode_paged=lambda params, tokens, caches, lengths: lm.decode_step_paged(
            params, cfg, tokens, caches, lengths),
    )
