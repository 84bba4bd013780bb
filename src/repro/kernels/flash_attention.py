"""Pallas TPU kernel: causal flash attention (online softmax, tiled Q/K).

The long-context prefill hot spot of the assigned architectures. Grid is
(batch*heads, Sq/BQ); each program streams KV tiles of size BK through
VMEM keeping the running (max, sumexp, acc) triple — O(S) memory instead
of O(S²). Tile sizes are MXU-aligned (BQ, BK multiples of 128; head_dim
padded to 128 lanes by the wrapper in ops.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bk: int,
                  causal: bool, sm_scale: float):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * sm_scale          # (BQ, D)
    S = k_ref.shape[1]
    nk = S // bk

    def body(carry, j):
        m_prev, l_prev, acc = carry
        # slice the ref, not a loaded value: Mosaic lowers a ref read at a
        # dynamic offset but has no dynamic_slice on vector values
        k = k_ref[0, pl.ds(pl.multiple_of(j * bk, bk), bk), :]
        v = v_ref[0, pl.ds(pl.multiple_of(j * bk, bk), bk), :]
        s = q @ k.astype(jnp.float32).T                   # (BQ, BK)
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, -1e30)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + p @ v.astype(jnp.float32)
        return (m_new, l_new, acc), None

    # iterate KV tiles up to (and including) the diagonal tile when causal
    upper = nk if not causal else jnp.minimum(((qi + 1) * bq + bk - 1) // bk, nk)
    m0 = jnp.full((bq,), -1e30, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, q.shape[1]), jnp.float32)

    def scan_body(j, carry):
        new_carry, _ = body(carry, j)
        return new_carry

    m, l, acc = jax.lax.fori_loop(0, upper, scan_body, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-20)[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, bq: int = 128,
                    bk: int = 128, interpret: bool | None = None):
    """q, k, v: (BH, S, D) — batch*heads flattened, same kv heads as q.
    Returns (BH, S, D)."""
    BH, S, D = q.shape
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    sm_scale = 1.0 / math.sqrt(D)
    grid = (BH, S // bq)
    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, causal=causal,
                               sm_scale=sm_scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        interpret=resolve_interpret(interpret),
    )(q, k, v)


# --------------------------------------------------------------------------- #
# paged-read decode attention (repro.serve KV blocks)
# --------------------------------------------------------------------------- #
def _paged_kernel(tab_ref, len_ref, q_ref, k_ref, v_ref, o_ref, *, bs: int,
                  sm_scale: float):
    """One (batch row, kv head) program: stream this row's KV blocks
    through the online-softmax triple. The fori_loop upper bound is the
    row's *live* block count (traced), so a short sequence reads only its
    own blocks — the paged win over a dense max_context scan."""
    L = len_ref[0, 0]                                    # row context length
    q = q_ref[0, 0].astype(jnp.float32) * sm_scale       # (G, D)
    nb = (L + bs - 1) // bs

    def body(j, carry):
        m_prev, l_prev, acc = carry
        bid = tab_ref[0, j]
        k = k_ref[pl.ds(bid, 1), :, 0, :][0].astype(jnp.float32)  # (bs, D)
        v = v_ref[pl.ds(bid, 1), :, 0, :][0].astype(jnp.float32)
        s = q @ k.T                                      # (G, bs)
        k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < L, s, -1e30)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + p @ v
        return m_new, l_new, acc

    G, D = q.shape
    m0 = jnp.full((G,), -1e30, jnp.float32)
    l0 = jnp.zeros((G,), jnp.float32)
    a0 = jnp.zeros((G, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nb, body, (m0, l0, a0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-20)[:, None]).astype(o_ref.dtype)


def paged_flash_attention(q, pool_k, pool_v, table, lengths, *,
                          interpret: bool | None = None):
    """Decode-step attention over a paged KV cache.

    q:       (B, K, G, D) — one query token per row, grouped GQA heads.
    pool_k/v:(NB, bs, K, D) block pools (shared across rows via the table).
    table:   (B, MAXB) int32 — row's logical block i lives in pool block
             table[row, i].
    lengths: (B,) int32 — valid context per row (entries at positions
             >= lengths[row] are masked; rows with length 0 return 0).
    Returns (B, K, G, D).

    The TPU compiler refuses this kernel today: the (1, MAXB) table block
    breaks the (8, 128) tiling rule, and compiling it needs the tables as
    scalar-prefetch operands. The serving default (the "gather" decode in
    models/layers.py) does not use it.
    """
    B, Kh, G, D = q.shape
    NB, bs = pool_k.shape[0], pool_k.shape[1]
    MAXB = table.shape[1]
    lengths2 = lengths.astype(jnp.int32).reshape(B, 1)
    kernel = functools.partial(_paged_kernel, bs=bs,
                               sm_scale=1.0 / math.sqrt(D))
    return pl.pallas_call(
        kernel,
        grid=(B, Kh),
        in_specs=[
            pl.BlockSpec((1, MAXB), lambda b, h: (b, 0)),
            pl.BlockSpec((1, 1), lambda b, h: (b, 0)),
            pl.BlockSpec((1, 1, G, D), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((NB, bs, 1, D), lambda b, h: (0, 0, h, 0)),
            pl.BlockSpec((NB, bs, 1, D), lambda b, h: (0, 0, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Kh, G, D), q.dtype),
        interpret=resolve_interpret(interpret),
    )(table.astype(jnp.int32), lengths2, q, pool_k, pool_v)
