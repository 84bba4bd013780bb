"""Error feedback (paper Algorithm 2, lines 6–8; Lemma 1).

The EF contract: the worker sends Q(m) where m = message + e_prev, and keeps
e_new = m - Q(m). Lemma 1 guarantees E||e||² ≤ 8η²(1-δ)(G²+σ²/B)/δ² so the
residual never accumulates unboundedly (validated in tests/test_error_feedback.py).

These helpers are per-leaf; `core.exchange` composes them with the
collective strategies, and `core.dqgan` lifts them over parameter pytrees.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import compressors as C


def ef_zeros_like(v, dtype=None):
    return jnp.zeros(v.shape, dtype or v.dtype)


FUSED_BLOCK = 1024  # kernel row width; must match kernels.quantize tiling


def fused_compatible(compressor, message) -> bool:
    """True when the Pallas fused EF+quantize kernel realizes exactly this
    compressor on this operand: linf quantization with one scale per
    FUSED_BLOCK elements, over a flat lane-aligned array (comm buckets are
    always shaped like this by construction). The level count is plumbed
    into the kernel, so the 8/4/2-bit block-1024 rungs of an adaptive
    PlanFamily — and `TracedQuant`, whose levels are a traced gather from
    the family's stacked table — all take the same fused path."""
    shaped = (getattr(message, "ndim", 0) == 1
              and message.shape[0] % FUSED_BLOCK == 0)
    if isinstance(compressor, C.TracedQuant):
        return compressor.per_block == FUSED_BLOCK and shaped
    return (isinstance(compressor, C.StochasticQuant)
            and compressor.norm == "linf"
            and compressor.per_block == FUSED_BLOCK
            and shaped)


def compress_with_ef(
    compressor: C.Compressor,
    message,
    e_prev,
    key,
    *,
    use_ef: bool = True,
    allow_fused: bool = True,
):
    """Compress (message + e_prev); return (payload, local dequant, e_new).

    With use_ef=False this is the CPOAdam-GQ baseline: the compression error
    is simply dropped (and, for biased compressors, convergence degrades —
    exactly the failure mode the paper's EF repairs).

    When the compressor/operand pair matches the fused Pallas kernel
    (fused_compatible — e.g. ``qsgd8_block1024`` over a comm bucket), the
    EF add, scale, stochastic round and residual write run in one
    VMEM-resident pass instead of ~4 jnp kernels. The payload format is
    identical; only the stochastic draws differ (same distribution).
    ``allow_fused=False`` opts out (e.g. under vmapped workers, where the
    pallas_call must not be batched).
    """
    if use_ef and allow_fused and fused_compatible(compressor, message):
        return fused_quantize_ef(message, e_prev, key,
                                 levels=compressor.levels)
    m = message + e_prev.astype(message.dtype) if use_ef else message
    payload = compressor.compress(m, key)
    m_hat = compressor.decompress(payload, m.shape, m.dtype)
    if use_ef:
        e_new = (m - m_hat).astype(e_prev.dtype)
    else:
        e_new = e_prev  # stays zero
    return payload, m_hat, e_new


def fused_quantize_ef(message_flat, e_prev, key, *, levels=127):
    """Single-HBM-pass EF + int8 quantization for a flat comm bucket via the
    Pallas kernel (kernels.quantize.quantize_ef_flat) — the fused equivalent
    of compress_with_ef(StochasticQuant(bits=8, norm="linf",
    per_block=FUSED_BLOCK), ...). Bucket sizes from comm.buckets are always
    lane-aligned, so no padding logic is needed here.

    Returns (payload {"codes","scale"}, m_hat, e_new) with the same contract
    as compress_with_ef; the payload is laid out exactly like the blocked
    StochasticQuant payload (codes (R, B) int8, scale (R, 1) f32), so
    ``StochasticQuant.decompress`` and the exchange collectives consume it
    unchanged.
    """
    from repro.kernels.quantize import quantize_ef_flat

    m32 = message_flat.astype(jnp.float32)
    rand = jax.random.uniform(key, m32.shape)
    codes, scales, e_new = quantize_ef_flat(
        m32, e_prev.astype(jnp.float32), rand, levels=levels)
    R = scales.shape[0]
    m_hat = (codes.reshape(R, -1).astype(jnp.float32)
             * (scales[:, None] / levels)).reshape(message_flat.shape)
    return ({"codes": codes.reshape(R, -1), "scale": scales.reshape(R, 1)},
            m_hat.astype(message_flat.dtype), e_new.astype(e_prev.dtype))


def lemma1_bound(eta, delta, G, sigma, B):
    """RHS of Lemma 1: 8η²(1-δ)(G² + σ²/B)/δ²."""
    return 8.0 * eta**2 * (1.0 - delta) * (G**2 + sigma**2 / B) / delta**2


def global_error_norm(e_tree):
    """||(1/M)Σ e^m||² proxy for a single worker's pytree: Σ_leaf ||e||²."""
    leaves = jax.tree.leaves(e_tree)
    return sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves)
