"""repro.sched: exchange schedules, straggler/participation simulation and
the wall-clock model (DESIGN.md §5), plus their core.dqgan integration —
local_k=1 must be bit-exact every_step, delayed must match the reference
staleness recursion, on 1 device here and on 8 forced-host devices via
the `multidevice` subprocess fixture."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sched as S
from repro.configs.base import DQConfig
from repro.core.dqgan import DQGAN

KEY = jax.random.key(0)

A = jnp.array(np.linalg.qr(np.random.RandomState(3).randn(6, 6))[0],
              jnp.float32)


def bilinear_field(params, batch, rng):
    del batch, rng
    x, y = params["x"], params["y"]
    return ({"x": A @ y, "y": -(A.T @ x)}, {"loss": x @ A @ y})


BASE = DQConfig(optimizer="omd", compressor="qsgd8_linf", exchange="sim",
                error_feedback=True, lr=0.05, worker_axes=())


def _run(dq, steps, field=bilinear_field, ret_state=False):
    tr = DQGAN(field_fn=field, dq=dq)
    st = tr.init({"x": jnp.ones(6), "y": jnp.ones(6)})
    step = jax.jit(tr.step, static_argnums=(3,))
    sched = S.get(dq.schedule, dq.local_k)
    for i in range(steps):
        st = step(st, None, KEY, sched.is_exchange_step(i)).state
    return jax.device_get(st if ret_state else st.params)


# --------------------------------------------------------------------------- #
# schedule arithmetic
# --------------------------------------------------------------------------- #
def test_schedule_helpers():
    es = S.get("every_step")
    assert es.period == 1 and es.staleness == 0
    assert all(es.is_exchange_step(i) for i in range(5))
    assert es.exchanges_in(7) == 7

    lk = S.get("local_k", 3)
    assert [lk.is_exchange_step(i) for i in range(7)] == [
        False, False, True, False, False, True, False]
    assert lk.exchanges_in(7) == 2
    assert [lk.round_index(i) for i in range(7)] == [0, 0, 0, 1, 1, 1, 2]

    dl = S.get("delayed")
    assert dl.staleness == 1 and dl.period == 1

    dl4 = S.get("delayed", tau=4)
    assert dl4.staleness == 4 and dl4.period == 1
    assert dl4.describe() == "delayed(tau=4)"
    assert S.get("delayed").describe() == "delayed"

    with pytest.raises(ValueError):
        S.get("bogus")
    with pytest.raises(ValueError):
        S.get("local_k", 0)
    with pytest.raises(ValueError):
        S.ExchangeSchedule("delayed", local_k=4)
    with pytest.raises(ValueError):
        S.get("delayed", tau=0)
    with pytest.raises(ValueError):
        S.ExchangeSchedule("local_k", 2, tau=3)
    with pytest.raises(ValueError):
        S.ExchangeSchedule("every_step", tau=2)


# --------------------------------------------------------------------------- #
# local_k
# --------------------------------------------------------------------------- #
def test_local_k1_is_bitexact_every_step():
    """K=1 rounds ARE every_step — bit-for-bit, through jit, with a
    stochastic compressor and EF in the loop."""
    p0 = _run(BASE, steps=25)
    p1 = _run(dataclasses.replace(BASE, schedule="local_k", local_k=1),
              steps=25)
    np.testing.assert_array_equal(p0["x"], p1["x"])
    np.testing.assert_array_equal(p0["y"], p1["y"])


def test_local_k_matches_accumulation_reference():
    """K=3 with the identity compressor + exact exchange must follow the
    hand-rolled recursion: messages accumulate locally, params move only
    at round ends by the accumulated update."""
    K, steps, eta = 3, 10, 0.05
    dq = dataclasses.replace(BASE, compressor="identity", exchange="exact",
                             schedule="local_k", local_k=K, lr=eta)
    got = _run(dq, steps=steps)

    w = {"x": np.ones(6, np.float32), "y": np.ones(6, np.float32)}
    gp = {"x": np.zeros(6, np.float32), "y": np.zeros(6, np.float32)}
    acc = {"x": np.zeros(6, np.float32), "y": np.zeros(6, np.float32)}
    An = np.asarray(A)
    for t in range(steps):
        wh = {k: w[k] - eta * gp[k] for k in w}
        g = {"x": An @ wh["y"], "y": -(An.T @ wh["x"])}
        for k in w:
            acc[k] += eta * g[k]
        if (t + 1) % K == 0:
            for k in w:
                w[k] -= acc[k]
                acc[k] = 0.0
        gp = g
    np.testing.assert_allclose(got["x"], w["x"], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got["y"], w["y"], rtol=1e-5, atol=1e-6)


def test_local_k_moves_params_only_at_round_ends():
    dq = dataclasses.replace(BASE, schedule="local_k", local_k=4)
    tr = DQGAN(field_fn=bilinear_field, dq=dq)
    st = tr.init({"x": jnp.ones(6), "y": jnp.ones(6)})
    step = jax.jit(tr.step, static_argnums=(3,))
    sched = S.get("local_k", 4)
    for i in range(4):
        prev = jax.device_get(st.params)
        st = step(st, None, KEY, sched.is_exchange_step(i)).state
        moved = not np.array_equal(jax.device_get(st.params)["x"], prev["x"])
        assert moved == sched.is_exchange_step(i), i
    # accumulator drained at the round end
    acc = jax.device_get(st.sched["accum"])
    assert all(np.all(a == 0) for a in jax.tree.leaves(acc))


def test_local_k_requires_static_do_exchange():
    dq = dataclasses.replace(BASE, schedule="local_k", local_k=2)
    tr = DQGAN(field_fn=bilinear_field, dq=dq)
    st = tr.init({"x": jnp.ones(6), "y": jnp.ones(6)})
    with pytest.raises(TypeError):
        jax.jit(tr.step)(st, None, KEY, jnp.array(True))


# --------------------------------------------------------------------------- #
# delayed
# --------------------------------------------------------------------------- #
def test_delayed_matches_reference_staleness_recursion():
    """Identity compressor + exact exchange: the delayed schedule must
    follow    w_half_t = w_{t-1} − P_t − η g_{t-1}
              w_t      = w_{t-1} − P_t          (apply the stale message)
              P_{t+1}  = η g_t                  (this step's message waits)
    where P is the pending buffer and the −P_t term in the lookahead is
    the staleness correction folded into the OMD extrapolation."""
    steps, eta = 12, 0.05
    dq = dataclasses.replace(BASE, compressor="identity", exchange="exact",
                             schedule="delayed", lr=eta)
    got = _run(dq, steps=steps)

    w = {"x": np.ones(6, np.float32), "y": np.ones(6, np.float32)}
    gp = {"x": np.zeros(6, np.float32), "y": np.zeros(6, np.float32)}
    P = {"x": np.zeros(6, np.float32), "y": np.zeros(6, np.float32)}
    An = np.asarray(A)
    for t in range(steps):
        wh = {k: w[k] - (eta * gp[k] + P[k]) for k in w}
        g = {"x": An @ wh["y"], "y": -(An.T @ wh["x"])}
        for k in w:
            w[k] -= P[k]
            P[k] = eta * g[k]
        gp = g
    np.testing.assert_allclose(got["x"], w["x"], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got["y"], w["y"], rtol=1e-5, atol=1e-6)


def test_delayed_first_step_applies_nothing():
    dq = dataclasses.replace(BASE, schedule="delayed")
    tr = DQGAN(field_fn=bilinear_field, dq=dq)
    st = tr.init({"x": jnp.ones(6), "y": jnp.ones(6)})
    out = jax.jit(tr.step, static_argnums=(3,))(st, None, KEY, True)
    np.testing.assert_array_equal(
        jax.device_get(out.state.params)["x"], np.ones(6, np.float32))
    pend = jax.device_get(out.state.sched["pending"])
    assert any(np.any(p != 0) for p in jax.tree.leaves(pend))


def test_delayed_still_converges_on_bilinear():
    """One step of staleness must not break the OMD contraction (the
    corrected lookahead keeps the extragradient structure)."""
    dq = dataclasses.replace(BASE, compressor="identity", exchange="exact",
                             schedule="delayed", lr=0.1,
                             error_feedback=False)
    p = _run(dq, steps=3000)
    dist = float(np.linalg.norm(p["x"]) + np.linalg.norm(p["y"]))
    assert dist < 0.05, dist


# --------------------------------------------------------------------------- #
# delayed(tau): the bounded-staleness parameter-server pipeline (DESIGN.md §8)
# --------------------------------------------------------------------------- #
def test_delayed_tau1_is_bitexact_delayed():
    """delayed(tau=1) IS PR 2's delayed — same single-slot layout, same
    compiled graph — bit-for-bit through jit with a stochastic compressor
    and EF in the loop."""
    p0 = _run(dataclasses.replace(BASE, schedule="delayed"), steps=25)
    p1 = _run(dataclasses.replace(BASE, schedule="delayed", staleness_tau=1),
              steps=25)
    np.testing.assert_array_equal(p0["x"], p1["x"])
    np.testing.assert_array_equal(p0["y"], p1["y"])


@pytest.mark.parametrize("tau", [1, 2, 3, 4])
def test_delayed_tau_matches_reference_recursion(tau):
    """Identity compressor + exact exchange: delayed(τ) must follow the
    τ-step recursion (the τ=1 case is PR 2's frozen delayed reference):
        w_half_t = w_{t-1} − Σ_j R_t[j] − η g_{t-1}
        w_t      = w_{t-1} − R_t[0]          (apply the τ-stale message)
        R_{t+1}  = [R_t[1:], η g_t]          (ring shift)
    """
    steps, eta = 14, 0.05
    dq = dataclasses.replace(BASE, compressor="identity", exchange="exact",
                             schedule="delayed", staleness_tau=tau, lr=eta,
                             error_feedback=False)
    got = _run(dq, steps=steps)

    An = np.asarray(A)
    w = {k: np.ones(6, np.float32) for k in "xy"}
    gp = {k: np.zeros(6, np.float32) for k in "xy"}
    R = {k: np.zeros((tau, 6), np.float32) for k in "xy"}
    for t in range(steps):
        wh = {k: w[k] - (eta * gp[k] + R[k].sum(0)) for k in w}
        g = {"x": An @ wh["y"], "y": -(An.T @ wh["x"])}
        for k in w:
            w[k] -= R[k][0]
            R[k] = np.concatenate([R[k][1:], (eta * g[k])[None]], 0)
        gp = g
    np.testing.assert_allclose(got["x"], w["x"], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got["y"], w["y"], rtol=1e-5, atol=1e-6)


def test_delayed_tau_warmup_and_staleness_metrics():
    """The first τ steps apply zero messages (pipeline fill) and the
    version vector makes the staleness metric read exactly τ."""
    tau = 3
    dq = dataclasses.replace(BASE, schedule="delayed", staleness_tau=tau)
    tr = DQGAN(field_fn=bilinear_field, dq=dq)
    st = tr.init({"x": jnp.ones(6), "y": jnp.ones(6)})
    assert st.sched["pending"]["x"].shape == (1, tau, 6)
    assert int(st.sched["versions"][0]) == -tau
    step = jax.jit(tr.step, static_argnums=(3,))
    for i in range(tau + 2):
        out = step(st, None, KEY, True)
        st = out.state
        m = jax.device_get(out.metrics)
        assert m["staleness_max"] == tau and m["staleness_mean"] == tau
        if i < tau:  # pipeline fill: nothing applied yet
            np.testing.assert_array_equal(
                jax.device_get(st.params)["x"], np.ones(6, np.float32))
    assert not np.array_equal(jax.device_get(st.params)["x"],
                              np.ones(6, np.float32))
    # every ring slot is a live in-flight message after warmup
    pend = jax.device_get(st.sched["pending"])
    assert all(np.all(np.any(p[0] != 0, axis=tuple(range(1, p[0].ndim))))
               for p in jax.tree.leaves(pend))


def test_staleness_tau_config_validation():
    with pytest.raises(ValueError):
        DQGAN(field_fn=bilinear_field,
              dq=dataclasses.replace(BASE, staleness_tau=2)).init(
                  {"x": jnp.ones(6), "y": jnp.ones(6)})
    with pytest.raises(ValueError):
        DQGAN(field_fn=bilinear_field,
              dq=dataclasses.replace(BASE, schedule="delayed",
                                     staleness_tau=0)).init(
                  {"x": jnp.ones(6), "y": jnp.ones(6)})


# --------------------------------------------------------------------------- #
# participation (host-side pieces; in-step semantics tested multidevice)
# --------------------------------------------------------------------------- #
def test_participation_counts_and_mask():
    assert S.n_participants(1.0, 8) == 8
    assert S.n_participants(0.5, 8) == 4
    assert S.n_participants(0.01, 8) == 1
    with pytest.raises(ValueError):
        S.n_participants(0.0, 8)
    with pytest.raises(ValueError):
        S.n_participants(1.5, 8)

    m0 = np.asarray(S.round_mask(KEY, 0, 8, 3))
    assert m0.sum() == 3 and set(np.unique(m0)) <= {0.0, 1.0}
    # deterministic per round, varies across rounds
    np.testing.assert_array_equal(m0, np.asarray(S.round_mask(KEY, 0, 8, 3)))
    masks = [tuple(np.asarray(S.round_mask(KEY, r, 8, 3))) for r in range(6)]
    assert len(set(masks)) > 1


# --------------------------------------------------------------------------- #
# stragglers + wall clock
# --------------------------------------------------------------------------- #
def test_straggler_profiles_deterministic():
    none = S.step_times(S.get_profile("none"), 8, 16, seed=0)
    np.testing.assert_array_equal(none, np.ones((16, 8)))
    a = S.step_times(S.get_profile("heavy"), 8, 16, seed=0)
    b = S.step_times(S.get_profile("heavy"), 8, 16, seed=0)
    np.testing.assert_array_equal(a, b)
    c = S.step_times(S.get_profile("heavy"), 8, 16, seed=1)
    assert not np.array_equal(a, c)
    assert (a > 0).all()
    with pytest.raises(ValueError):
        S.get_profile("nope")


def test_clock_schedule_ordering_under_stragglers():
    """The acceptance-criterion inequality: local_k and delayed beat
    every_step per step once comm costs anything, and stragglers widen
    the local_k gap (max-of-sums < sum-of-maxes)."""
    prof = S.get_profile("mild")
    for M in (4, 8, 16):
        times = S.step_times(prof, M, 64, seed=0, base=1e-3)
        t_ex = 2e-3
        every = S.simulate(S.get("every_step"), times, t_ex)
        local = S.simulate(S.get("local_k", 4), times, t_ex)
        delay = S.simulate(S.get("delayed"), times, t_ex)
        assert local["mean_step_s"] < every["mean_step_s"], M
        assert delay["mean_step_s"] < every["mean_step_s"], M
        assert every["n_exchanges"] == 64 and local["n_exchanges"] == 16


def test_clock_delayed_hides_comm_under_compute():
    times = np.ones((32, 8)) * 1e-3
    # comm far cheaper than compute: delayed pays (almost) compute only
    out = S.simulate(S.get("delayed"), times, 1e-5)
    assert out["mean_step_s"] == pytest.approx(1e-3, rel=0.05)
    # comm dominating: delayed pays (almost) comm only, every_step both
    slow = S.simulate(S.get("delayed"), times, 1e-1)
    every = S.simulate(S.get("every_step"), times, 1e-1)
    assert slow["mean_step_s"] == pytest.approx(1e-1, rel=0.05)
    assert every["mean_step_s"] == pytest.approx(1e-1 + 1e-3, rel=0.01)


def test_clock_participation_gates_barrier_on_fewer_workers():
    prof = S.get_profile("heavy")
    times = S.step_times(prof, 8, 64, seed=3, base=1e-3)
    full = S.simulate(S.get("every_step"), times, 1e-3, participation=1.0)
    half = S.simulate(S.get("every_step"), times, 1e-3, participation=0.5)
    assert half["mean_step_s"] < full["mean_step_s"]


def test_speedup_vs_M_monotone_compute_term():
    prof = S.get_profile("none")
    rows = S.speedup_vs_M(S.get("delayed"), prof, (1, 2, 4, 8), steps=32,
                          t_compute_single=1e-2,
                          bytes_fn=lambda M: 1e5)
    sp = [r["speedup"] for r in rows]
    assert sp[0] == pytest.approx(1.0)
    assert sp[-1] > sp[0]


def test_baseline_mean_step_shared_across_schedules():
    """The hoisted M=1 baseline (benchmarks.run bugfix): with one worker
    and no comm every schedule walks the same compute times, so the
    shared baseline must equal each schedule's own M=1 simulation."""
    prof = S.get_profile("mild")
    base = S.baseline_mean_step(prof, 48, 2e-3, seed=3)
    for sch in (S.get("every_step"), S.get("local_k", 4), S.get("delayed"),
                S.get("delayed", tau=4)):
        own = S.time_per_step(sch, prof, 1, 48, 2e-3, 0.0,
                              seed=3)["mean_step_s"]
        assert own == pytest.approx(base, rel=1e-12), sch.describe()


# --------------------------------------------------------------------------- #
# versioned parameter server (sched.server, DESIGN.md §8)
# --------------------------------------------------------------------------- #
def test_versioned_server_semantics():
    srv = S.VersionedServer(n_workers=4, tau=2)
    assert [srv.pull(m) for m in range(4)] == [0, 0, 0, 0]
    for m in range(4):
        assert srv.push(m) == 0
    assert srv.version == 1          # one round = n_workers pushes
    # worker 0 never re-pulls: staleness grows one version per round; its
    # round-3 push lands exactly AT the bound (staleness 2), then trips it
    for _ in range(2):
        for m in range(4):
            if m:
                srv.pull(m)
            srv.push(m)
    assert srv.staleness(0) == 3 and not srv.can_push(0)
    with pytest.raises(S.StalenessBoundExceeded):
        srv.push(0)                  # 3 versions behind: bound trips
    srv.pull(0)
    assert srv.push(0) == 0          # re-pull resets the staleness
    with pytest.raises(ValueError):
        S.VersionedServer(n_workers=4, tau=0)


def test_server_partial_rounds():
    srv = S.VersionedServer(n_workers=4, tau=1, n_round=2)
    srv.pull(0), srv.pull(1)
    srv.push(0)
    assert srv.version == 0
    srv.push(0)                      # duplicate: same round, not a close
    assert srv.version == 0
    srv.push(1)
    assert srv.version == 1          # 2 DISTINCT participants close a round


ADVERSARIAL = S.StragglerProfile("adversarial", slowdown_sigma=1.0,
                                 jitter_sigma=0.3, spike_prob=0.3,
                                 spike_factor=20.0)


@pytest.mark.parametrize("profile",
                         [S.get_profile("heavy"), ADVERSARIAL])
def test_push_pull_staleness_bounded(profile):
    """The SSP gate: whatever the stragglers do, no applied contribution
    is ever more than τ versions stale — and the extra slack makes the
    modeled clock monotone non-increasing in τ."""
    times = S.step_times(profile, 8, 96, seed=11, base=1e-3)
    prev_total = None
    for tau in (1, 2, 4, 8):
        out = S.simulate_push_pull(times, 2e-3, tau)
        assert out["staleness_max"] <= tau, (tau, out["staleness_max"])
        assert out["staleness_mean"] <= out["staleness_max"]
        assert out["n_exchanges"] == 96
        if prev_total is not None:
            assert out["total_s"] <= prev_total * (1 + 1e-9), tau
        prev_total = out["total_s"]
    # determinism
    a = S.simulate_push_pull(times, 2e-3, 4)
    b = S.simulate_push_pull(times, 2e-3, 4)
    np.testing.assert_array_equal(a["per_step_s"], b["per_step_s"])


def test_push_pull_participation_staleness_consistent():
    """Under partial participation a round's aggregate can be *ready*
    before a straggler-gated earlier round — the server still applies
    versions in order, so the staleness bookkeeping stays valid and the
    participant bound ≤ τ holds."""
    times = S.step_times(ADVERSARIAL, 8, 96, seed=5, base=1e-3)
    for tau in (1, 2, 4):
        out = S.simulate_push_pull(times, 2e-3, tau, participation=0.5)
        assert out["staleness_max"] <= tau, (tau, out["staleness_max"])
        assert 0.0 <= out["staleness_mean"] <= out["staleness_max"]
        full = S.simulate_push_pull(times, 2e-3, tau)
        assert out["total_s"] <= full["total_s"] * (1 + 1e-9)


def test_clock_routes_delayed_tau_to_server_dataflow():
    times = S.step_times(S.get_profile("mild"), 8, 32, seed=0, base=1e-3)
    auto = S.simulate(S.get("delayed", tau=4), times, 2e-3)
    forced = S.simulate_push_pull(times, 2e-3, 4)
    assert auto["tau"] == 4
    np.testing.assert_array_equal(auto["per_step_s"], forced["per_step_s"])
    # delayed(1) default stays on PR 2's synchronous pipelined model ...
    sync = S.simulate(S.get("delayed"), times, 2e-3)
    assert "tau" not in sync
    # ... unless the server dataflow is forced (the τ-frontier sweep)
    srv1 = S.simulate(S.get("delayed"), times, 2e-3, dataflow="server")
    assert srv1["tau"] == 1 and srv1["staleness_max"] <= 1
    with pytest.raises(ValueError):
        S.simulate(S.get("delayed"), times, 2e-3, dataflow="bogus")
    # only delayed has a push/pull loop to model
    with pytest.raises(ValueError):
        S.simulate(S.get("local_k", 4), times, 2e-3, dataflow="server")
    with pytest.raises(ValueError):
        S.simulate(S.get("every_step"), times, 2e-3, dataflow="server")


def test_benchmark_regression_gate():
    """Rows match on the structural strategy hash (PR 4), not the
    schedule/compressor label strings."""
    from benchmarks.run import check_sched_regression

    base = {"rows": [{"schedule": "delayed", "compressor": "8bit", "M": 8,
                      "strategy": "aaa111", "mean_step_s": 1.0,
                      "wire_mb": 10.0}],
            "tau_frontier": [{"tau": 4, "strategy": "bbb222",
                              "mean_step_s": 0.5, "wire_mb": 5.0}]}
    ok = {"rows": [{"schedule": "delayed", "compressor": "8bit", "M": 8,
                    "strategy": "aaa111", "mean_step_s": 1.05,
                    "wire_mb": 10.0}],
          "tau_frontier": [{"tau": 4, "strategy": "bbb222",
                            "mean_step_s": 0.4, "wire_mb": 5.0}]}
    assert check_sched_regression(ok, base) == []
    bad = {"rows": [{"schedule": "delayed", "compressor": "8bit", "M": 8,
                     "strategy": "aaa111", "mean_step_s": 1.2,
                     "wire_mb": 10.0}],
           "tau_frontier": [{"tau": 4, "strategy": "bbb222",
                             "mean_step_s": 0.5, "wire_mb": 5.6}]}
    fails = check_sched_regression(bad, base)
    assert len(fails) == 2
    assert any("mean_step_s" in f for f in fails)
    assert any("tau_frontier" in f and "wire_mb" in f for f in fails)
    # new rows (no baseline counterpart) never gate — including a row
    # whose LABELS match the baseline but whose strategy differs
    # structurally (this was a bogus comparison under name matching);
    # at least one row must still match or the gate refuses outright
    extra = {"rows": [{"schedule": "delayed", "compressor": "8bit", "M": 8,
                       "strategy": "aaa111", "mean_step_s": 1.0,
                       "wire_mb": 10.0},
                      {"schedule": "delayed", "compressor": "8bit", "M": 8,
                       "strategy": "ccc333", "mean_step_s": 9.9,
                       "wire_mb": 99.0}]}
    assert check_sched_regression(extra, base) == []
    # a baseline predating the strategy hashes is refused outright
    legacy = {"rows": [{"schedule": "delayed", "compressor": "8bit",
                        "M": 8, "mean_step_s": 1.0, "wire_mb": 10.0}]}
    fails = check_sched_regression(ok, legacy)
    assert len(fails) == 1 and "pre-strategy" in fails[0]


def test_mixture_gan_schedule_overrides_smoke():
    """The tau-frontier convergence path: train_mixture_gan must accept
    dq_overrides and drive the schedule-aware step (static do_exchange)
    for delayed(tau) — a 3-step smoke so CI catches plumbing breaks
    without paying the full frontier sweep."""
    from benchmarks.gan_common import train_mixture_gan

    final, curve, st = train_mixture_gan(
        "DQGAN", steps=3, batch=32,
        dq_overrides={"schedule": "delayed", "staleness_tau": 2})
    assert {"modes", "hq_frac", "fid"} <= set(final)
    # every pending leaf carries the (worker, τ) ring axes
    assert all(l.shape[:2] == (1, 2)
               for l in jax.tree.leaves(st.sched["pending"]))
    assert int(jax.device_get(st.step)) == 3


def test_benchmark_gate_rejects_tier_mismatch(tmp_path):
    """Running the gate at a different tier than the baseline (wire_mb
    scales with steps) must exit with a config error, not spurious
    regressions."""
    import json
    import os
    import subprocess
    import sys as _sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "experiments/baselines/"
                                 "sched_quick.json")) as f:
        doctored = json.load(f)
    doctored["steps"] = 256          # pretend the baseline was full-tier
    bad = tmp_path / "sched_full_baseline.json"
    bad.write_text(json.dumps(doctored))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = (os.path.join(repo, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [_sys.executable, "-m", "benchmarks.run", "--quick", "--only",
         "sched", "--check-against", str(bad)],
        capture_output=True, text=True, timeout=300, cwd=repo, env=env)
    assert proc.returncode == 2, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "tier mismatch" in proc.stdout


def test_bench_roofline_rows_ride_the_sink(tmp_path):
    """The roofline section is a first-class benchmarks.run citizen: one
    row per dry-run record through row() -> bench_row events, explicit
    reporting when the records are absent (never a silent skip)."""
    import json as _json

    import benchmarks.run as BR
    from repro import obs

    rec = {"arch": "gemma-2b", "shape": "train_4k", "mesh": "16x16",
           "layout": "dp", "status": "ok", "params": 2e9, "chips": 256,
           "mf": 1e15, "analytic_flops": 1.5e15, "flops": 1e12,
           "bottleneck": "collective",
           "roofline": {"compute_s": 1e-3, "memory_s": 2e-3,
                        "collective_s": 5e-3}}
    d = tmp_path / "dryrun"
    d.mkdir()
    (d / "a.json").write_text(_json.dumps(rec))
    path = str(tmp_path / "bench.jsonl")
    BR._SINK = obs.make_sink(path)
    try:
        recs = BR.bench_roofline(True, dirpath=str(d))
        # a missing records dir is itself a reported row
        none = BR.bench_roofline(True, dirpath=str(tmp_path / "absent"))
    finally:
        BR._SINK.close()
        BR._SINK = None
    assert len(recs) == 1 and none == []
    evs = [e for e in obs.read_events(path) if e["kind"] == "bench_row"]
    assert [e["name"] for e in evs] == [
        "roofline/gemma-2b/train_4k/16x16", "roofline/none"]
    assert "bottleneck=collective" in evs[0]["derived"]
    assert "no dry-run records" in evs[1]["derived"]
    assert (tmp_path / "roofline.md").exists()


# --------------------------------------------------------------------------- #
# ledger schedule columns
# --------------------------------------------------------------------------- #
def test_ledger_counts_rounds_not_steps():
    from repro.comm import CommLedger
    from repro.core import compressors as C

    led = CommLedger()
    led.register("t", "sim", C.get("qsgd8_linf"), (64, 64), 8)
    per = led.wire_bytes_per_step
    sched = S.get("local_k", 4)
    for i in range(8):
        led.tick(exchanged=sched.is_exchange_step(i), wall_s=0.5)
    assert led.steps == 8 and led.rounds == 2
    assert led.cumulative_wire_bytes == pytest.approx(2 * per)
    assert led.sim_clock_s == pytest.approx(4.0)
    s = led.summary()
    assert s["rounds"] == 2 and s["sim_clock_s"] == pytest.approx(4.0)


# --------------------------------------------------------------------------- #
# multidevice: 8 forced-host workers, shard_map + vmap SPMD paths
# --------------------------------------------------------------------------- #
SCHED_EQUIV_SCRIPT = r"""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.configs.base import DQConfig
from repro.core.dqgan import DQGAN
from repro import sched as S

A = jnp.array(np.random.RandomState(0).randn(4,4), jnp.float32)
def field(params, batch, rng):
    x, y = params["x"], params["y"]
    s = 1.0 + jnp.mean(batch)           # worker-dependent data
    return {"x": s * (A @ y), "y": -s * (A.T @ x)}, {"loss": x @ A @ y}

mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
params = {"x": jnp.ones(4), "y": jnp.ones(4)}
pspecs = {"x": P(), "y": P()}
batch = jnp.arange(8, dtype=jnp.float32).reshape(8, 1) / 8.0

def run(dq, steps=16):
    tr = DQGAN(field_fn=field, dq=dq, mesh=mesh, param_specs=pspecs,
               batch_spec=P(("data",)))
    sched = S.get(dq.schedule, dq.local_k)
    with jax.set_mesh(mesh):
        st = tr.init(params)
        step = jax.jit(tr.step, static_argnums=(3,))
        for i in range(steps):
            st = step(st, batch, jax.random.key(7),
                      sched.is_exchange_step(i)).state
        return jax.device_get(st.params)

base = DQConfig(optimizer="omd", compressor="qsgd8_linf", exchange="sim",
                lr=0.05, worker_axes=("data",))
for spmd in ("shard_map", "vmap"):
    b = dataclasses.replace(base, spmd=spmd)
    p0 = run(b)
    p1 = run(dataclasses.replace(b, schedule="local_k", local_k=1))
    np.testing.assert_array_equal(p0["x"], p1["x"])
    np.testing.assert_array_equal(p0["y"], p1["y"])
    # delayed(tau=1) is bit-exact PR 2 delayed (stochastic compressor + EF)
    d0 = run(dataclasses.replace(b, schedule="delayed"))
    d1 = run(dataclasses.replace(b, schedule="delayed", staleness_tau=1))
    np.testing.assert_array_equal(d0["x"], d1["x"])
    np.testing.assert_array_equal(d0["y"], d1["y"])

# delayed(tau), uncompressed, against the M-worker reference recursion
# (tau=1 is PR 2's frozen delayed reference; tau=2 exercises the ring).
# identity+'sim' IS the exact mean, and unlike 'exact' it composes with
# spmd='vmap' (non-sim exchange kinds there are refused since PR 4).
An = np.asarray(A); eta = 0.05; M = 8
scales = 1.0 + np.arange(M) / 8.0   # mean of each worker's batch slice
for spmd in ("shard_map", "vmap"):
    for tau in (1, 2):
        dq = dataclasses.replace(base, spmd=spmd, compressor="identity",
                                 exchange="sim", schedule="delayed",
                                 staleness_tau=tau, error_feedback=False)
        got = run(dq, steps=10)

        w = {k: np.ones(4, np.float32) for k in "xy"}
        gp = [{k: np.zeros(4, np.float32) for k in "xy"} for _ in range(M)]
        Rd = [{k: np.zeros((tau, 4), np.float32) for k in "xy"}
              for _ in range(M)]
        for t in range(10):
            gs = []
            for m in range(M):
                wh = {k: w[k] - (eta * gp[m][k] + Rd[m][k].sum(0))
                      for k in w}
                gs.append({"x": scales[m] * (An @ wh["y"]),
                           "y": -scales[m] * (An.T @ wh["x"])})
            qh = {k: np.mean([Rd[m][k][0] for m in range(M)], axis=0)
                  for k in w}
            for k in w:
                w[k] = w[k] - qh[k]
            for m in range(M):
                Rd[m] = {k: np.concatenate([Rd[m][k][1:],
                                            (eta * gs[m][k])[None]], 0)
                         for k in w}
                gp[m] = gs[m]
        np.testing.assert_allclose(got["x"], w["x"], rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(got["y"], w["y"], rtol=1e-4, atol=1e-5)
print("OK")
"""


@pytest.mark.multidevice
def test_sched_equivalences_8dev(multidevice):
    out = multidevice(SCHED_EQUIV_SCRIPT)
    assert "OK" in out


PARTICIPATION_SCRIPT = r"""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.configs.base import DQConfig
from repro.core.dqgan import DQGAN
from repro import sched as S

A = jnp.array(np.random.RandomState(0).randn(4,4), jnp.float32)
def field(params, batch, rng):
    x, y = params["x"], params["y"]
    s = 1.0 + jnp.mean(batch)
    return {"x": s * (A @ y), "y": -s * (A.T @ x)}, {"loss": x @ A @ y}

mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
params = {"x": jnp.ones(4), "y": jnp.ones(4)}
batch = jnp.arange(8, dtype=jnp.float32).reshape(8, 1) / 8.0
key = jax.random.key(7)
M, eta = 8, 0.05

# (identity + 'sim' is numerically the exact mean; partial participation
# with exchange='exact' is refused at Strategy construction since PR 4)
dq = DQConfig(optimizer="omd", compressor="identity", exchange="sim",
              error_feedback=True, lr=eta, worker_axes=("data",),
              participation=0.5)
tr = DQGAN(field_fn=field, dq=dq, mesh=mesh,
           param_specs={"x": P(), "y": P()}, batch_spec=P(("data",)))
with jax.set_mesh(mesh):
    st = tr.init(params)
    out = jax.jit(tr.step, static_argnums=(3,))(st, batch, key, True)
st1 = jax.device_get(out.state)

# reference: q_hat = mean over the round's participants only; the workers
# sitting out keep their message in the EF residual.
mask = np.asarray(S.round_mask(key, 0, M, S.n_participants(0.5, M)))
assert mask.sum() == 4
An = np.asarray(A)
scales = 1.0 + np.arange(M) / 8.0
gs = [{"x": scales[m] * (An @ np.ones(4, np.float32)),
       "y": -scales[m] * (An.T @ np.ones(4, np.float32))} for m in range(M)]
part = [m for m in range(M) if mask[m] == 1.0]
qh = {k: np.mean([eta * gs[m][k] for m in part], axis=0) for k in "xy"}
np.testing.assert_allclose(st1.params["x"], 1.0 - qh["x"], rtol=1e-5,
                           atol=1e-6)
np.testing.assert_allclose(st1.params["y"], 1.0 - qh["y"], rtol=1e-5,
                           atol=1e-6)

# EF: participants untouched (identity => zero residual), absentees carry
# their unsent message eta*g
for m in range(M):
    for k in "xy":
        e1 = np.asarray(st1.ef[k]["e1"])[m]
        want = np.zeros(4) if mask[m] == 1.0 else eta * gs[m][k]
        np.testing.assert_allclose(e1, want, rtol=1e-5, atol=1e-6)
print("OK")
"""


@pytest.mark.multidevice
def test_participation_semantics_8dev(multidevice):
    out = multidevice(PARTICIPATION_SCRIPT)
    assert "OK" in out


PARTICIPATION_TAU_SCRIPT = r"""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.configs.base import DQConfig
from repro.core.dqgan import DQGAN
from repro import sched as S

A = jnp.array(np.random.RandomState(0).randn(4,4), jnp.float32)
def field(params, batch, rng):
    x, y = params["x"], params["y"]
    s = 1.0 + jnp.mean(batch)
    return {"x": s * (A @ y), "y": -s * (A.T @ x)}, {"loss": x @ A @ y}

mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
params = {"x": jnp.ones(4), "y": jnp.ones(4)}
batch = jnp.arange(8, dtype=jnp.float32).reshape(8, 1) / 8.0
key = jax.random.key(7)
M, eta, tau, steps = 8, 0.05, 2, 6
n = S.n_participants(0.5, M)

dq = DQConfig(optimizer="omd", compressor="identity", exchange="sim",
              error_feedback=True, lr=eta, worker_axes=("data",),
              schedule="delayed", staleness_tau=tau, participation=0.5)
tr = DQGAN(field_fn=field, dq=dq, mesh=mesh,
           param_specs={"x": P(), "y": P()}, batch_spec=P(("data",)))
with jax.set_mesh(mesh):
    st = tr.init(params)
    step = jax.jit(tr.step, static_argnums=(3,))
    stale_maxes = []
    for i in range(steps):
        out = step(st, batch, key, True)
        st = out.state
        stale_maxes.append(float(jax.device_get(out.metrics)["staleness_max"]))
got = jax.device_get(st)

# numpy reference: delayed(tau) ring x count-exact participation. A
# participant sends its ring head + residual (identity: sent exactly,
# residual drains); a skipper sends zero and folds the head into e1 —
# the skipped round extends its staleness (version not advanced) while
# the ring stays clamped at depth tau.
masks = [np.asarray(S.round_mask(key, t, M, n)) for t in range(steps)]
An = np.asarray(A)
scales = 1.0 + np.arange(M) / 8.0
w = {k: np.ones(4, np.float32) for k in "xy"}
gp = [{k: np.zeros(4, np.float32) for k in "xy"} for _ in range(M)]
Rd = [{k: np.zeros((tau, 4), np.float32) for k in "xy"} for _ in range(M)]
e1 = [{k: np.zeros(4, np.float32) for k in "xy"} for _ in range(M)]
ver = np.full(M, -tau)
ref_stale_max = []
for t in range(steps):
    mask = masks[t]
    gs = []
    for m in range(M):
        wh = {k: w[k] - (eta * gp[m][k] + e1[m][k] + Rd[m][k].sum(0))
              for k in w}
        gs.append({"x": scales[m] * (An @ wh["y"]),
                   "y": -scales[m] * (An.T @ wh["x"])})
    part = [m for m in range(M) if mask[m] == 1.0]
    qh = {k: np.mean([Rd[m][k][0] + e1[m][k] for m in part], axis=0)
          for k in w}
    for k in w:
        w[k] = w[k] - qh[k]
    for m in range(M):
        for k in w:
            if mask[m] != 1.0:
                e1[m][k] = e1[m][k] + Rd[m][k][0]   # unsent head rides EF
            else:
                e1[m][k] = np.zeros(4, np.float32)  # identity: drained
            Rd[m][k] = np.concatenate([Rd[m][k][1:],
                                       (eta * gs[m][k])[None]], 0)
        if mask[m] == 1.0:
            ver[m] = t - tau
        gp[m] = gs[m]
    ref_stale_max.append(float((t - ver).max()))

np.testing.assert_allclose(got.params["x"], w["x"], rtol=1e-5, atol=1e-6)
np.testing.assert_allclose(got.params["y"], w["y"], rtol=1e-5, atol=1e-6)
for m in range(M):
    for k in "xy":
        np.testing.assert_allclose(np.asarray(got.ef[k]["e1"])[m],
                                   e1[m][k], rtol=1e-5, atol=1e-6)
# version vector: skipped rounds count toward staleness, participants
# reset to exactly tau
np.testing.assert_array_equal(np.asarray(got.sched["versions"]), ver)
assert stale_maxes == ref_stale_max, (stale_maxes, ref_stale_max)
assert max(stale_maxes) > tau       # someone actually skipped a round
print("OK")
"""


@pytest.mark.multidevice
def test_participation_tau_composition_8dev(multidevice):
    """participation × τ: a skipped round extends that worker's staleness
    (version vector frozen) while its unsent ring head is preserved in
    the EF residual — asserted against a full numpy reference."""
    out = multidevice(PARTICIPATION_TAU_SCRIPT)
    assert "OK" in out
