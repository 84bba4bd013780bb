"""Pallas kernel sweeps vs the pure-jnp oracles (interpret=True on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.quantize import quantize_ef_blocked
from repro.kernels.ref import flash_attention_ref, quantize_ef_ref

KEY = jax.random.key(0)


@pytest.mark.parametrize("rows,cols", [(8, 128), (64, 256), (256, 512),
                                       (32, 1024)])
@pytest.mark.parametrize("e_dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_ef_matches_ref(rows, cols, e_dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    g = 0.3 * jax.random.normal(k1, (rows, cols), jnp.float32)
    e = (0.05 * jax.random.normal(k2, (rows, cols))).astype(e_dtype)
    r = jax.random.uniform(k3, (rows, cols), jnp.float32)
    br = min(rows, 64)
    while rows % br:
        br //= 2
    codes, scale, e_new = quantize_ef_blocked(g, e, r, block_rows=br)
    codes_r, scale_r, e_new_r = quantize_ef_ref(g, e, r)
    np.testing.assert_array_equal(np.asarray(codes), np.asarray(codes_r))
    np.testing.assert_allclose(np.asarray(scale), np.asarray(scale_r),
                               rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(e_new, np.float32), np.asarray(e_new_r, np.float32),
        rtol=1e-2, atol=1e-3)


def test_quantize_ef_reconstruction_bound():
    """codes*scale/levels must reconstruct g+e within one quantization bin."""
    g = jax.random.normal(KEY, (128, 256))
    e = jnp.zeros_like(g)
    r = jax.random.uniform(jax.random.fold_in(KEY, 1), g.shape)
    codes, scale, e_new = quantize_ef_blocked(g, e, r)
    deq = codes.astype(jnp.float32) * scale / 127.0
    err = jnp.abs(deq - g)
    bin_size = scale / 127.0
    assert bool(jnp.all(err <= bin_size + 1e-6))
    np.testing.assert_allclose(np.asarray(g - deq), np.asarray(e_new),
                               atol=1e-6)


@pytest.mark.parametrize("S", [128, 256, 512])
@pytest.mark.parametrize("D", [64, 128])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(S, D, causal):
    q = jax.random.normal(KEY, (2, S, 2, D), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (2, S, 2, D))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (2, S, 2, D))
    ref = flash_attention_ref(q, k, v, causal=causal)
    qf = q.transpose(0, 2, 1, 3).reshape(4, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(4, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(4, S, D)
    out = flash_attention(qf, kf, vf, causal=causal, bq=128, bk=128)
    out = out.reshape(2, 2, S, D).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_bf16():
    S, D = 256, 128
    mk = lambda i: jax.random.normal(jax.random.fold_in(KEY, i),
                                     (2, S, D)).astype(jnp.bfloat16)
    q, k, v = mk(0), mk(1), mk(2)
    out = flash_attention(q, k, v, causal=True)
    ref = flash_attention_ref(q[:, :, None].swapaxes(1, 2).swapaxes(1, 2).reshape(2, S, 1, D),
                              k.reshape(2, S, 1, D), v.reshape(2, S, 1, D))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref.reshape(2, S, D), np.float32),
                               rtol=2e-2, atol=2e-2)
    assert out.dtype == jnp.bfloat16


def test_resolve_interpret_by_platform(monkeypatch):
    """One dispatch rule: interpret on cpu, compiled on tpu, an explicit
    bool wins, and any other platform is an error (no silent fallback)."""
    import repro.kernels as K

    assert K.resolve_interpret() is True            # this suite runs on cpu
    assert K.resolve_interpret(False) is False
    monkeypatch.setattr(K.jax, "default_backend", lambda: "tpu")
    assert K.resolve_interpret() is False
    assert K.resolve_interpret(True) is True
    monkeypatch.setattr(K.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        K.resolve_interpret()
