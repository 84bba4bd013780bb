"""repro.comm: bucket layout, layer-wise planner, wire ledger, and the
bucketed exchange path (DESIGN.md §3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import comm
from repro.configs.base import DQConfig
from repro.core import compressors as C
from repro.core import exchange as X
from repro.core.dqgan import DQGAN
from repro.models.gan import GANConfig, dcgan_init, gan_field_fn, mlp_gan_init

KEY = jax.random.key(0)


# --------------------------------------------------------------------------- #
# bucket layout
# --------------------------------------------------------------------------- #
def test_layout_alignment_and_roundtrip():
    params = dcgan_init(KEY, GANConfig())
    W = 8
    layout = comm.layout_for_params(params, n_workers=W, bucket_bytes=1 << 20)
    assert len(layout.buckets) > 1 and not layout.skipped
    align = W * comm.buckets.LANE * comm.buckets.SUBLANE
    for b in layout.buckets:
        assert b.size % align == 0          # worker-divisible AND lane-aligned
        assert b.size % W == 0
        # slots tile the bucket contiguously from offset 0
        off = 0
        for s in b.slots:
            assert s.offset == off
            off += s.size
        assert off == b.used <= b.size
    # every leaf appears exactly once
    seen = sorted(s.index for b in layout.buckets for s in b.slots)
    assert seen == list(range(layout.n_leaves))

    leaves, _ = jax.tree.flatten(params)
    flats = comm.pack(layout, leaves)
    assert all(f.shape == (b.size,) for f, b in zip(flats, layout.buckets))
    back = comm.unpack_into(layout, flats, leaves)
    for a, b in zip(leaves, back):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_layout_skips_sharded_leaves():
    from jax.sharding import PartitionSpec as P

    shapes = {"w": (64, 32), "b": (64,)}
    specs = {"w": P("model", None), "b": P()}
    layout = comm.build_layout(shapes, specs, n_workers=4)
    assert len(layout.skipped) == 1 and layout.skipped[0].shape == (64, 32)
    assert sum(len(b.slots) for b in layout.buckets) == 1


# --------------------------------------------------------------------------- #
# plan_for_tree fallbacks (satellite) vs bucketing
# --------------------------------------------------------------------------- #
def test_plan_for_tree_fallbacks_and_bucketing_removes_them():
    from jax.sharding import PartitionSpec as P

    W = 8
    shapes = {"odd_vec": (33,), "good_mat": (16, 32), "prime": (7, 3),
              "sharded": (64, 32)}
    specs = {"odd_vec": P(), "good_mat": P(), "prime": P(),
             "sharded": P("model", None)}
    plans = X.plan_for_tree("two_phase", shapes, specs, W)
    # no worker-divisible unsharded axis -> sim fallback
    assert plans["odd_vec"]["fallback"] and plans["odd_vec"]["strategy"] == "sim"
    assert plans["prime"]["fallback"]
    # (16, 32): axis 1 divisible by 8 and unsharded -> real two_phase
    assert not plans["good_mat"]["fallback"]
    assert plans["good_mat"]["chunk_axis"] == 1
    # sharded spec blocks axis 0; axis 1 (32) still works
    assert not plans["sharded"]["fallback"]

    # bucketing: every unsharded leaf lands in a bucket whose padded size is
    # divisible by W -> zero fallbacks regardless of leaf shapes
    layout = comm.build_layout(shapes, specs, n_workers=W)
    bucketed_idx = {s.index for b in layout.buckets for s in b.slots}
    assert len(bucketed_idx) == 3           # all but "sharded"
    for b in layout.buckets:
        pb = X.plan_bucket("two_phase", b.size, W)
        assert pb["strategy"] == "two_phase" and not pb["fallback"]

    # and the ledger agrees: seed planner has fallbacks, bucketed has none
    led_seed = comm.CommLedger.from_tree("two_phase", "qsgd8_linf",
                                         shapes, specs, W)
    cplan = comm.plan_comm(layout, "qsgd8_linf", "uniform")
    led_buck = comm.CommLedger.from_plan(layout, cplan, "two_phase", W,
                                         "qsgd8_linf",
                                         leaf_plans=[plans["sharded"]])
    assert led_seed.n_fallbacks() == 2
    assert led_buck.n_fallbacks() == 0
    # without leaf plans the skipped (sharded) leaf is accounted
    # conservatively as a sim fallback
    led_cons = comm.CommLedger.from_plan(layout, cplan, "two_phase", W,
                                         "qsgd8_linf")
    assert led_cons.n_fallbacks() == 1


# --------------------------------------------------------------------------- #
# planner policies
# --------------------------------------------------------------------------- #
def _dcgan_layout(W=8):
    params = dcgan_init(KEY, GANConfig())
    return comm.layout_for_params(params, n_workers=W, bucket_bytes=1 << 20)


def test_planner_uniform():
    layout = _dcgan_layout()
    plan = comm.plan_comm(layout, "qsgd8_linf", "uniform")
    assert all(a.compressor == "qsgd8_linf" for a in plan.assignments)
    assert plan.payload_bytes > 0


def test_planner_size_tiered_protects_small_buckets():
    # bias/norm-sized tensors only -> the whole bucket stays full precision
    shapes = {"b1": (64,), "b2": (128,), "w": (1 << 18,)}
    layout = comm.build_layout(shapes, None, n_workers=2, bucket_bytes=1 << 12)
    plan = comm.plan_comm(layout, "qsgd8_linf", "size_tiered")
    small = [a for b, a in zip(layout.buckets, plan.assignments)
             if all(s.size < comm.planner.SMALL_ELEMS for s in b.slots)]
    big = [a for b, a in zip(layout.buckets, plan.assignments)
           if any(s.size >= comm.planner.SMALL_ELEMS for s in b.slots)]
    assert small and all(a.compressor == "identity" for a in small)
    assert big and all(a.compressor == "qsgd8_linf" for a in big)


def test_planner_delta_budget_meets_budget():
    layout = _dcgan_layout()
    base = comm.plan_comm(layout, "qsgd8_linf", "uniform")
    # generous budget: stays at the base compressor
    rich = comm.plan_comm(layout, "qsgd8_linf", "delta_budget",
                          budget_bytes=2 * base.payload_bytes)
    assert all(a.compressor == "qsgd8_linf" for a in rich.assignments)
    # tight budget: downgrades until under budget, δ degrades monotonically
    tight = comm.plan_comm(layout, "qsgd8_linf", "delta_budget",
                           budget_bytes=base.payload_bytes // 2)
    assert tight.payload_bytes <= base.payload_bytes // 2
    assert tight.min_delta <= rich.min_delta


def test_planner_rejects_unknown_policy():
    with pytest.raises(ValueError):
        comm.plan_comm(_dcgan_layout(), "qsgd8_linf", "bogus")


# --------------------------------------------------------------------------- #
# ledger
# --------------------------------------------------------------------------- #
def test_ledger_allgather_matches_analytic_wire_model():
    """Acceptance: CommLedger byte counts == Compressor.wire_bytes analytic
    model for the allgather strategy (send own + receive W-1 others)."""
    W = 8
    comp = C.get("qsgd8_linf")
    shape = (4096,)
    led = comm.CommLedger()
    led.register("t", "allgather", comp, shape, W)
    expected = comp.wire_bytes(shape, W) * W
    assert led.wire_bytes_per_step == expected
    assert led.wire_bytes_per_step == X.modeled_wire_bytes(
        "allgather", comp, shape, W)
    # int8 codes + f32 scale: carried == analytic for the 8-bit quantizer
    assert led.carried_bytes_per_step == expected


def test_ledger_carried_vs_wire_for_subbyte_codes():
    # sign codes ride in int8 (1B) but model 1 bit on the wire -> carried ≈ 8x
    led = comm.CommLedger()
    led.register("t", "allgather", C.get("sign"), (8192,), 4)
    assert led.carried_bytes_per_step > 6 * led.wire_bytes_per_step


def test_ledger_accumulation_and_ratio():
    led = comm.CommLedger()
    led.register("t", "two_phase", C.get("qsgd8_linf"), (1 << 16,), 8)
    led.tick(10)
    s = led.summary()
    assert s["steps"] == 10
    assert s["cumulative_wire_bytes"] == 10 * s["wire_bytes_per_step"]
    # 8-bit codes vs f32: achieved ratio ≈ 4x under the same collective
    assert 3.5 < s["compression_ratio"] < 4.5


def test_payload_nbytes_matches_manual_count():
    comp = C.get("qsgd8_block256")
    shape = (1000,)
    n_scales = -(-1000 // 256)
    assert comm.payload_nbytes(comp, shape) == 1024 * 1 + 4 * n_scales


# --------------------------------------------------------------------------- #
# bucketed exchange numerics (single worker; multi-worker below)
# --------------------------------------------------------------------------- #
def _mk_trainer(comm_plan, exchange, compressor, ef=True, **kw):
    cfg = GANConfig(name="mix", image_size=0, data_dim=2, latent_dim=16,
                    hidden=128)
    dq = DQConfig(optimizer="omd", compressor=compressor, exchange=exchange,
                  error_feedback=ef, lr=1e-3, worker_axes=(),
                  comm_plan=comm_plan, bucket_mb=0.25, **kw)
    return DQGAN(field_fn=gan_field_fn(cfg), dq=dq), cfg


def test_bucketed_identity_equals_per_tensor_single_worker():
    tr_b, cfg = _mk_trainer("uniform", "sim", "identity")
    tr_n, _ = _mk_trainer("none", "sim", "identity")
    params = mlp_gan_init(KEY, cfg)
    batch = {"real": jax.random.normal(KEY, (64, 2))}
    st_b, st_n = tr_b.init(params), tr_n.init(params)
    for i in range(3):
        k = jax.random.fold_in(KEY, i)
        st_b = jax.jit(tr_b.step)(st_b, batch, k).state
        st_n = jax.jit(tr_n.step)(st_n, batch, k).state
    for a, b in zip(jax.tree.leaves(st_b.params), jax.tree.leaves(st_n.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_bucketed_qsgd_within_delta_bound():
    """The bucketed compress of a message tree stays a δ-contraction
    (δ ≥ 0.9 for linf 8-bit, as in test_compressors) — padding and tensor
    fusion must not break Definition 1."""
    tr, _ = _mk_trainer("uniform", "sim", "qsgd8_linf", ef=False)
    message = {"a": 0.1 * jax.random.normal(KEY, (128, 64)),
               "b": jax.random.normal(jax.random.fold_in(KEY, 1), (33,))}
    plans = tr._plans(message)
    errs, l2 = [], float(sum(jnp.sum(v**2) for v in jax.tree.leaves(message)))
    for i in range(8):
        qhat, _ = tr._exchange_tree(message, None, plans,
                                    jax.random.fold_in(KEY, 10 + i), ())
        err = sum(float(jnp.sum((q - m) ** 2))
                  for q, m in zip(jax.tree.leaves(qhat),
                                  jax.tree.leaves(message)))
        errs.append(err)
    assert np.mean(errs) <= (1 - 0.9) * l2 + 1e-6


def test_bucketed_two_phase_ef_state_structure():
    tr, cfg = _mk_trainer("uniform", "two_phase", "qsgd8_linf")
    params = mlp_gan_init(KEY, cfg)
    st = tr.init(params)
    assert set(st.ef.keys()) == {"leaf", "bucket"}
    layout, _ = tr._comm(params)
    assert set(st.ef["bucket"].keys()) == {str(b.bid) for b in layout.buckets}
    # training remains finite and EF residuals are bounded
    batch = {"real": jax.random.normal(KEY, (64, 2))}
    for i in range(5):
        out = jax.jit(tr.step)(st, batch, jax.random.fold_in(KEY, i))
        st = out.state
    m = jax.device_get(out.metrics)
    assert np.isfinite(m["loss"]) and np.isfinite(m["error_norm"])
    assert m["error_norm"] > 0  # EF is live


def test_comm_ledger_from_trainer_counts_fallbacks():
    cfg = GANConfig()  # dcgan32: conv biases are not 8-divisible
    dq_seed = DQConfig(exchange="two_phase", compressor="qsgd8_linf",
                       worker_axes=("data",))
    dq_buck = DQConfig(exchange="two_phase", compressor="qsgd8_linf",
                       worker_axes=("data",), comm_plan="uniform")

    class FakeMesh:
        shape = {"data": 8}
    params = dcgan_init(KEY, cfg)
    tr_seed = DQGAN(field_fn=gan_field_fn(cfg), dq=dq_seed, mesh=FakeMesh())
    tr_buck = DQGAN(field_fn=gan_field_fn(cfg), dq=dq_buck, mesh=FakeMesh())
    n_seed = tr_seed.comm_ledger(params).n_fallbacks()
    n_buck = tr_buck.comm_ledger(params).n_fallbacks()
    assert n_seed > 0 and n_buck == 0


# --------------------------------------------------------------------------- #
# fused kernel over bucket tiles
# --------------------------------------------------------------------------- #
def test_quantize_ef_flat_matches_blocked_ref():
    from repro.kernels.quantize import quantize_ef_flat
    from repro.kernels.ref import quantize_ef_ref

    n = 4 * 1024
    g = 0.3 * jax.random.normal(KEY, (n,))
    e = 0.05 * jax.random.normal(jax.random.fold_in(KEY, 1), (n,))
    r = jax.random.uniform(jax.random.fold_in(KEY, 2), (n,))
    codes, scales, e_new = quantize_ef_flat(g, e, r)
    assert codes.shape == (n,) and scales.shape == (n // 1024,)
    cr, sr, er = quantize_ef_ref(g.reshape(-1, 1024), e.reshape(-1, 1024),
                                 r.reshape(-1, 1024))
    np.testing.assert_array_equal(np.asarray(codes), np.asarray(cr).reshape(n))
    np.testing.assert_allclose(np.asarray(scales),
                               np.asarray(sr).reshape(-1), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(e_new),
                               np.asarray(er).reshape(n), atol=1e-6)


def test_fused_quantize_ef_contract():
    from repro.core.error_feedback import fused_quantize_ef

    n = 2 * 1024
    m = jax.random.normal(KEY, (n,))
    e = 0.1 * jax.random.normal(jax.random.fold_in(KEY, 1), (n,))
    payload, m_hat, e_new = fused_quantize_ef(m, e, jax.random.fold_in(KEY, 2))
    np.testing.assert_allclose(np.asarray(m + e - m_hat), np.asarray(e_new),
                               atol=1e-5)
    assert payload["codes"].dtype == jnp.int8
    # payload is wire-compatible with the blocked StochasticQuant: the
    # compressor's own decompress reconstructs the kernel's m_hat
    comp = C.get("qsgd8_block1024")
    deq = comp.decompress(payload, (n,), jnp.float32)
    np.testing.assert_allclose(np.asarray(deq), np.asarray(m_hat), atol=1e-6)


def test_compress_with_ef_dispatches_to_fused_kernel():
    """qsgd8_block1024 over a flat lane-aligned bucket routes through the
    Pallas kernel and honors the EF contract; a non-aligned operand takes
    the plain path with the same (payload, m_hat, e_new) interface."""
    from repro.core.error_feedback import compress_with_ef, fused_compatible

    comp = C.get("qsgd8_block1024")
    flat = jax.random.normal(KEY, (4 * 1024,))
    e = jnp.zeros_like(flat)
    assert fused_compatible(comp, flat)
    payload, m_hat, e_new = compress_with_ef(comp, flat, e, KEY)
    np.testing.assert_allclose(np.asarray(flat - m_hat), np.asarray(e_new),
                               atol=1e-6)
    assert payload["codes"].shape == (4, 1024)

    ragged = jax.random.normal(KEY, (1000,))
    assert not fused_compatible(comp, ragged)
    _, m_hat2, _ = compress_with_ef(comp, ragged, jnp.zeros_like(ragged), KEY)
    assert m_hat2.shape == ragged.shape


def test_bucketed_training_with_fused_compressor():
    tr, cfg = _mk_trainer("uniform", "two_phase", "qsgd8_block1024")
    params = mlp_gan_init(KEY, cfg)
    st = tr.init(params)
    batch = {"real": jax.random.normal(KEY, (64, 2))}
    for i in range(3):
        out = jax.jit(tr.step)(st, batch, jax.random.fold_in(KEY, i))
        st = out.state
    m = jax.device_get(out.metrics)
    assert np.isfinite(m["loss"]) and np.isfinite(m["error_norm"])


# --------------------------------------------------------------------------- #
# multi-worker equivalence (8 forced host devices, subprocess)
# --------------------------------------------------------------------------- #
BUCKETED_EQUIV_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.configs.base import DQConfig
from repro.core.dqgan import DQGAN
from repro.models.gan import GANConfig, mlp_gan_init, gan_field_fn

mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
cfg = GANConfig(name="mix", image_size=0, data_dim=2, latent_dim=16, hidden=128)
key = jax.random.key(0)
params = mlp_gan_init(key, cfg)

def run(comm_plan, exch, comp, steps=4):
    dq = DQConfig(optimizer="omd", compressor=comp, exchange=exch,
                  error_feedback=True, lr=1e-2, worker_axes=("data",),
                  comm_plan=comm_plan, bucket_mb=0.25)
    tr = DQGAN(field_fn=gan_field_fn(cfg), dq=dq, mesh=mesh,
               batch_spec=P(("data",)))
    with jax.set_mesh(mesh):
        st = tr.init(params)
        step = jax.jit(tr.step)
        for i in range(steps):
            batch = {"real": jax.random.normal(jax.random.fold_in(key, i), (64, 2))}
            st = step(st, batch, jax.random.key(7)).state
    return jax.device_get(st.params)

# identity: bucketed == per-tensor for every strategy (exact semantics)
for exch in ("sim", "allgather", "two_phase", "exact"):
    p_none = run("none", exch, "identity")
    p_buck = run("uniform", exch, "identity")
    for a, b in zip(jax.tree.leaves(p_none), jax.tree.leaves(p_buck)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                                   err_msg=exch)

# quantized: bucketed runs stay near the exact trajectory (δ-bounded drift)
p_exact = run("none", "exact", "identity")
for exch in ("sim", "allgather", "two_phase"):
    p_q = run("uniform", exch, "qsgd8_linf")
    d = sum(float(np.linalg.norm(np.asarray(a) - np.asarray(b)))
            for a, b in zip(jax.tree.leaves(p_exact), jax.tree.leaves(p_q)))
    assert np.isfinite(d) and d < 1.0, (exch, d)
print("OK")
"""


@pytest.mark.multidevice
def test_bucketed_exchange_multiworker_equivalence(multidevice):
    out = multidevice(BUCKETED_EQUIV_SCRIPT)
    assert "OK" in out
