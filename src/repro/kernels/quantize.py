"""Pallas TPU kernel: fused error-feedback + stochastic int8 quantization.

This is the paper's per-step compute hot spot: every gradient element is
read, compensated (m = g + e), scaled, stochastically rounded to an int8
level, and the fresh residual written back — ~13 bytes of HBM traffic per
element when unfused (g, e reads; codes, scale, e' writes — plus the jnp
intermediates). The fused kernel does one VMEM-resident pass:

    per (BR, C) tile:  m = g + e
                       s = rowmax(|m|)
                       q = floor(m/s*L) + (rand < frac)     (stochastic)
                       e' = m - q*s/L

Tiles are (BR, C) with C a multiple of 128 (lane width) and BR a multiple
of 8 (sublane) — MXU/VPU-aligned per the TPU tiling rules. Randomness is
passed in as a uniform tensor so the kernel is bit-reproducible in the
interpreter (CPU) and compiled (TPU) alike; on TPU the pltpu PRNG could
generate it in-kernel and save one read stream.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _qef_body(g, e, r, levels, e_dtype):
    """Shared tile body: EF add, row scale, stochastic round, residual.
    `levels` is a Python int (static kernel) or an f32 scalar read from
    the dynamic-levels operand (PlanFamily dispatch, DESIGN.md §10)."""
    m = g.astype(jnp.float32) + e.astype(jnp.float32)
    s = jnp.max(jnp.abs(m), axis=1, keepdims=True) + 1e-20   # (BR, 1)
    lv = m / s * levels
    low = jnp.floor(lv)
    up = (r < (lv - low)).astype(jnp.float32)
    q = low + up
    return q.astype(jnp.int8), s, (m - q * (s / levels)).astype(e_dtype)


def _quantize_ef_kernel(g_ref, e_ref, r_ref, codes_ref, scale_ref, enew_ref,
                        *, levels: int):
    codes, s, e_new = _qef_body(g_ref[...], e_ref[...], r_ref[...], levels,
                                enew_ref.dtype)
    codes_ref[...] = codes
    scale_ref[...] = s
    enew_ref[...] = e_new


def _quantize_ef_kernel_dyn(g_ref, e_ref, r_ref, lv_ref, codes_ref,
                            scale_ref, enew_ref):
    """Dynamic-levels variant: the level count arrives as a (1, 1) f32
    operand (a gather from the PlanFamily's stacked bit-width table), so
    one compiled kernel serves every member of an adaptive family."""
    codes, s, e_new = _qef_body(g_ref[...], e_ref[...], r_ref[...],
                                lv_ref[0, 0], enew_ref.dtype)
    codes_ref[...] = codes
    scale_ref[...] = s
    enew_ref[...] = e_new


def quantize_ef_blocked(g, e, rand, *, levels=127, block_rows: int = 256,
                        interpret: bool | None = None):
    """g, e, rand: (R, C) with C % 128 == 0. Blocks are (block_rows, C)
    with block_rows a multiple of 8 or R itself; when block_rows does not
    divide R the last block is ragged — rows are independent, so its
    out-of-range rows are computed on padding and their writes dropped.
    Returns (codes int8 (R,C), scales f32 (R,1), e_new (R,C)).

    ``levels`` may be a Python int (baked into the kernel — the original
    path, compiled graph unchanged) or a traced scalar (routed through
    the dynamic-levels kernel as a (1, 1) operand)."""
    R, C = g.shape
    assert C % 128 == 0, f"lane-align C to 128, got {C}"
    br = min(block_rows, R)
    assert br == R or br % 8 == 0, f"block rows {br} of {R}: not 8-aligned"
    grid = (pl.cdiv(R, br),)
    interpret = resolve_interpret(interpret)

    def idx(i):
        return (i, 0)

    out_specs = [
        pl.BlockSpec((br, C), idx),
        pl.BlockSpec((br, 1), idx),
        pl.BlockSpec((br, C), idx),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((R, C), jnp.int8),
        jax.ShapeDtypeStruct((R, 1), jnp.float32),
        jax.ShapeDtypeStruct((R, C), e.dtype),
    ]
    tile = pl.BlockSpec((br, C), idx)
    if isinstance(levels, int):
        kernel = functools.partial(_quantize_ef_kernel, levels=levels)
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[tile, tile, tile],
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=interpret,
        )(g, e, rand)
    lv = jnp.asarray(levels, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        _quantize_ef_kernel_dyn,
        grid=grid,
        in_specs=[tile, tile, tile,
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(g, e, rand, lv)


def bucket_tile_shape(n: int):
    """(R, C, block_rows) tiling for a flat comm bucket of n elements.
    buckets.build_layout pads every bucket to a multiple of
    n_workers·LANE·SUBLANE = n_workers·1024, so C = 1024 always divides.
    The block-row count is R itself up to 256 rows (a full-extent block is
    always legal), else 256 — a multiple of 8, as the TPU's (8, 128)
    tiling rule asks of every operand, the int8 codes included — with a
    ragged last block where 256 does not divide R."""
    C = 1024 if n % 1024 == 0 else 128
    assert n % C == 0, f"bucket size {n} not lane-aligned"
    R = n // C
    return R, C, min(256, R)


def quantize_ef_flat(g, e, rand, *, levels=127,
                     interpret: bool | None = None):
    """Fused quantize+EF over a flat comm bucket (1-D, lane-aligned size).

    Tiles the bucket as (R, 1024) rows — each row is one scale block, i.e.
    the bucket-shaped equivalent of StochasticQuant(bits=8, per_block=1024)
    with the residual update fused into the same VMEM pass.
    Returns (codes (n,) int8, scales (R,) f32, e_new (n,))."""
    n = g.shape[0]
    R, C, br = bucket_tile_shape(n)
    codes, scale, e_new = quantize_ef_blocked(
        g.reshape(R, C), e.reshape(R, C), rand.reshape(R, C),
        levels=levels, block_rows=br, interpret=interpret)
    return codes.reshape(n), scale.reshape(R), e_new.reshape(n)
