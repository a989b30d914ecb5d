"""Buffered-async contracts (``core.async_fl`` + both backends).

The async subsystem's guarantees, mirroring the fault/participation
suites:

  * the ARRIVAL stream is counter-based and bit-shared: the NumPy helper
    and the JAX in-scan block produce identical (2, N) uniforms, distinct
    from every other stream's draws, and hit the configured delivery /
    staleness statistics,
  * ``AsyncSpec``/``resolve`` validate and normalize the async knobs
    identically for both backends; the resolved tables (staleness CDF,
    discounts, delivery weights, payload scales) are consistent with each
    other,
  * ``async_round`` realizes exactly the stationary model the tables
    price, and ``stale_replace`` is the single last-gradient path shared
    with ``fault.on_missing="stale"`` (bit-identical to the inline
    ``np.where`` replay it replaced),
  * engine-vs-oracle parity holds with async on (zero / stale /
    designed weights), alone and composed with participation + faults,
  * ``run.mode="sync"`` is a strict no-op (bit-identical to a trainer
    that never heard of async), and ``rng="fast"`` stays bit-identical
    for counter-only schemes / statistically equivalent otherwise,
  * the co-design solver (``core.sca_jax.solve_async_batch``) returns
    feasible capped-simplex weights that beat uniform on its own
    bound-shaped objective,
  * in the K=1 regime (pure Bernoulli thinning — the model Theorem 1
    covers exactly) the measured steady-state error sits below the
    Theorem-1 bound at the async effective participation levels,
  * ``run.mode`` / ``async_.*`` are sweepable axes that change the cell
    hash (schema v7), with pre-v7 dict back-compat.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import async_fl as A
from repro.core import baselines as B
from repro.core import rngstream, sca_jax
from repro.core.bounds import (async_bias_sum, async_effective_participation,
                               theorem1_bound)
from repro.core.channel import WirelessConfig, make_deployment
from repro.core.faults import FaultSpec
from repro.data.loader import FLDataset
from repro.data.partition import partition_by_class
from repro.data.synthetic import SyntheticSpec, make_classification_dataset
from repro.fl.tasks import SoftmaxRegressionTask
from repro.fl.parity import assert_parity
from repro.fl.trainer import FLTrainer, solve_w_star

N_DEVICES = 10
ROUNDS = 20
TRIALS = 2
EVAL_EVERY = 5
N_TEST = 10 * 30     # 10 classes x n_test_per_class

ASPEC = A.AsyncSpec(buffer_rounds=3, arrival_rate=0.6,
                    rate_heterogeneity=2.0, staleness_discount=0.8)


@pytest.fixture(scope="module")
def setup():
    spec = SyntheticSpec(n_train_per_class=100, n_test_per_class=30,
                         noise_sigma=1.5)
    x_tr, y_tr, x_te, y_te = make_classification_dataset(spec)
    shards = partition_by_class(x_tr, y_tr, N_DEVICES, 1, 100, seed=3)
    ds = FLDataset.from_shards(shards, x_te, y_te)
    task = SoftmaxRegressionTask(n_features=784, mu=0.01, g_max=20.0)
    dep = make_deployment(WirelessConfig(n_devices=N_DEVICES, seed=1))
    eta = 0.5 / (task.mu + task.smooth_l)
    return task, ds, dep, eta


def _vanilla(setup):
    task, _, dep, _ = setup
    return B.VanillaOTA(task.dim, task.g_max, dep.cfg.energy_per_symbol,
                        dep.cfg.noise_power)


# ---------------------------------------------------- ARRIVAL stream

class TestStream:
    @pytest.mark.parametrize("seed,trial,t", [(0, 0, 0), (5, 1, 7),
                                              (123, 3, 999)])
    def test_np_matches_jax_bitwise(self, seed, trial, t):
        """The NumPy oracle helper and the engine's in-scan block draw
        the SAME threefry counters — identical bits, not just close."""
        u_np = rngstream.arrival_block_np(seed, trial, t, 64)
        key = rngstream.arrival_base_key(seed, trial)
        u_jx = np.asarray(rngstream.arrival_block(key, t, 64))
        assert u_np.dtype == np.float64 and u_np.shape == (2, 64)
        np.testing.assert_array_equal(u_np, u_jx)
        assert np.all((u_np >= 0.0) & (u_np < 1.0))

    def test_distinct_from_other_streams(self):
        """ARRIVAL is its own tagged stream: same (seed, trial, t)
        counters, different draws than FAULT and PARTICIPATE."""
        u_arr = rngstream.arrival_block_np(5, 1, 7, 64)
        assert not np.array_equal(u_arr[0],
                                  rngstream.participation_block_np(5, 1, 7,
                                                                   64))
        assert not np.array_equal(u_arr[:2],
                                  rngstream.fault_block_np(5, 1, 7, 64)[:2])

    def test_deterministic(self):
        a = rngstream.arrival_block_np(9, 2, 13, 32)
        b = rngstream.arrival_block_np(9, 2, 13, 32)
        np.testing.assert_array_equal(a, b)

    def test_delivery_rate(self):
        """deliver = (u0 < r) hits the target arrival rate to 4 sigma."""
        r = 0.6
        rounds, n = 400, 64
        hits = sum(
            float(np.sum(rngstream.arrival_block_np(2, 0, t, n)[0] < r))
            for t in range(rounds))
        mean = hits / (rounds * n)
        sigma = np.sqrt(r * (1 - r) / (rounds * n))
        assert abs(mean - r) <= 4.0 * sigma

    def test_staleness_distribution(self):
        """Counting crossed CDF thresholds realizes the geometric pmf:
        the fraction of fresh draws (S = 0) matches P(S=0) = r to
        4 sigma."""
        r, k = 0.45, 4
        cdf = A.staleness_cdf(np.full(16, r), k)
        rounds, n = 400, 16
        fresh = sum(
            float(np.sum((rngstream.arrival_block_np(3, 0, t, n)[1][None, :]
                          >= cdf).sum(axis=0) == 0))
            for t in range(rounds))
        mean = fresh / (rounds * n)
        sigma = np.sqrt(r * (1 - r) / (rounds * n))
        assert abs(mean - r) <= 4.0 * sigma

    def test_key_cache_is_bounded_and_stable(self):
        cache = rngstream._ARRIVAL_KEY_CACHE
        before = rngstream.arrival_block_np(7, 0, 3, 16)
        for s in range(rngstream._KEY_CACHE_MAX + 50):
            rngstream.arrival_block_np(10_000 + s, 0, 0, 4)
        assert len(cache) <= rngstream._KEY_CACHE_MAX
        after = rngstream.arrival_block_np(7, 0, 3, 16)
        np.testing.assert_array_equal(before, after)


# ----------------------------------------------- spec / resolve / tables

class TestSpecResolve:
    def test_spec_validation(self):
        with pytest.raises(ValueError, match="buffer_rounds"):
            A.AsyncSpec(buffer_rounds=0)
        with pytest.raises(ValueError, match="arrival_rate"):
            A.AsyncSpec(arrival_rate=0.0)
        with pytest.raises(ValueError, match="rate_heterogeneity"):
            A.AsyncSpec(rate_heterogeneity=-1.0)
        with pytest.raises(ValueError, match="staleness_discount"):
            A.AsyncSpec(staleness_discount=1.5)
        with pytest.raises(ValueError, match="on_missing"):
            A.AsyncSpec(on_missing="drop")
        with pytest.raises(ValueError, match="weighting"):
            A.AsyncSpec(weighting="inverse")

    def test_sync_is_none(self):
        assert A.resolve("sync", ASPEC, 8) is None
        assert A.resolve("sync", None, 8) is None
        with pytest.raises(ValueError, match="mode is 'sync'"):
            A.resolve("sync", ASPEC, 8, weights=np.ones(8))
        with pytest.raises(ValueError, match="mode must be"):
            A.resolve("semi", ASPEC, 8)

    def test_designed_needs_weights(self):
        asp = dataclasses.replace(ASPEC, weighting="designed")
        with pytest.raises(ValueError, match="explicit async_weights"):
            A.resolve("async", asp, 8)

    def test_weights_validation(self):
        with pytest.raises(ValueError, match="shape"):
            A.resolve("async", ASPEC, 8, weights=np.ones(7))
        with pytest.raises(ValueError, match="finite and > 0"):
            bad = np.ones(8); bad[0] = 0.0
            A.resolve("async", ASPEC, 8, weights=bad)
        with pytest.raises(ValueError, match="sum"):
            A.resolve("async", ASPEC, 8, weights=np.full(8, 0.5))

    def test_resolved_hashable_and_tables(self):
        res = A.resolve("async", ASPEC, 8)
        assert {res: "hashable"}[res] == "hashable"
        r = res.rates_array()
        assert np.all(r[:-1] <= r[1:] + 1e-15)       # device 0 slowest
        cdf = res.cdf_array()
        assert cdf.shape == (3, 8)
        assert np.all(np.diff(cdf, axis=0) >= 0.0)   # CDF rows increase
        pmf = A.staleness_pmf(r, 3)
        np.testing.assert_allclose(pmf.sum(axis=0), cdf[-1], rtol=1e-12)
        np.testing.assert_allclose(
            res.discounts_array(), 0.8 ** np.arange(3), rtol=1e-12)
        # the payload normalization keeps E[delivered mass] at N
        c = res.delivery_weight_array()
        np.testing.assert_allclose(
            float(np.sum(c * res.payload_scale_array())), 8.0, rtol=1e-12)

    def test_delivery_weight_monotone_in_rate(self):
        """Faster devices deliver more discounted mass: c_m increases
        with r_m, and a deeper buffer never loses mass."""
        c = A.delivery_weight(ASPEC, 8)
        assert np.all(np.diff(c) >= 0.0) and c[0] < c[-1]
        deeper = dataclasses.replace(ASPEC, buffer_rounds=6)
        assert np.all(A.delivery_weight(deeper, 8) >= c - 1e-15)

    def test_expected_staleness_decreases_with_rate(self):
        sbar = A.expected_staleness(ASPEC, 8)
        assert np.all(np.diff(sbar) <= 0.0) and sbar[0] > sbar[-1]
        assert np.all((sbar >= 0.0) & (sbar <= ASPEC.buffer_rounds - 1))

    def test_synchronous_limit(self):
        """arrival_rate=1: every device delivers fresh every round —
        c = 1, sbar = 0, payload scale = v."""
        asp = A.AsyncSpec(buffer_rounds=4, arrival_rate=1.0)
        np.testing.assert_allclose(A.delivery_weight(asp, 6), 1.0,
                                   rtol=1e-12)
        np.testing.assert_allclose(A.expected_staleness(asp, 6), 0.0,
                                   atol=1e-15)


# ------------------------------------------------- async_round semantics

class TestAsyncRound:
    def test_known_realization(self):
        """Hand-built uniforms force every path: fresh, stale, out of
        window, and no-delivery."""
        n, k, d = 4, 2, 3
        res = A.resolve("async",
                        A.AsyncSpec(buffer_rounds=k, arrival_rate=0.5,
                                    staleness_discount=0.5), n)
        rates = res.rates_array()                    # all 0.5
        cdf = res.cdf_array()                        # rows: 0.5, 0.75
        g_old = np.arange(n * d, dtype=np.float64).reshape(n, d)
        g_new = g_old + 100.0
        buf = np.zeros((k, n, d)); buf[0] = g_old
        #        dev0 fresh   dev1 stale-1  dev2 out     dev3 silent
        u = np.array([[0.1,        0.2,        0.3,        0.9],
                      [0.1,        0.6,        0.8,        0.1]])
        payload, ok, buf2 = A.async_round(g_new, buf, u, rates, cdf,
                                          res.discounts_array(),
                                          res.payload_scale_array())
        scale = res.payload_scale_array()
        np.testing.assert_array_equal(ok, [True, True, False, False])
        np.testing.assert_allclose(payload[0], g_new[0] * scale[0])
        np.testing.assert_allclose(payload[1], g_old[1] * 0.5 * scale[1])
        np.testing.assert_array_equal(buf2[0], g_new)   # shifted window
        np.testing.assert_array_equal(buf2[1], g_old)

    def test_stale_replace_matches_inline_where(self):
        """The unified last-gradient path is bit-identical to the inline
        ``np.where`` replay it replaced (fault.on_missing='stale')."""
        rng = np.random.default_rng(0)
        g_last_ref = np.zeros((6, 4))
        g_last_new = np.zeros((6, 4))
        for _ in range(20):
            g = rng.normal(size=(6, 4))
            ok = rng.random(6) < 0.6
            ref = np.where(ok[:, None], g, g_last_ref)   # PR-8 inline form
            g_last_ref = ref
            out, g_last_new = A.stale_replace(g, ok, g_last_new)
            np.testing.assert_array_equal(out, ref)
            np.testing.assert_array_equal(g_last_new, ref)


# ------------------------------------------------------ co-design solver

class TestSolver:
    def test_feasible_and_beats_uniform(self):
        """Heterogeneous arrivals: the designed v is on the capped
        simplex and strictly improves the bound-shaped objective over
        uniform weights (evaluated with the same formula)."""
        n = 12
        asp = A.AsyncSpec(buffer_rounds=4, arrival_rate=0.5,
                          rate_heterogeneity=4.0, staleness_discount=0.8)
        p = np.full(n, 1.0 / n)
        c = A.delivery_weight(asp, n)
        sbar = A.expected_staleness(asp, n)
        wv, wb = 50.0, 1e3

        def obj(v):
            e = p * c * v * (n / np.sum(c * v))
            return (wb * np.sum((e - 1.0 / n) ** 2)
                    + wv * (1.0 / np.sum(e) ** 2 + np.sum(e ** 2 * sbar)))

        v, j = sca_jax.solve_async_batch(p[None], c[None], sbar[None],
                                         [wv], [wb])
        v, j = v[0], float(j[0])
        assert abs(v.sum() - n) < 1e-6
        assert np.all(v > 0.0) and np.all(v <= n + 1e-9)
        np.testing.assert_allclose(j, obj(v), rtol=1e-8)
        assert j < obj(np.ones(n))
        # bias-dominant weights rebalance toward the slow devices
        assert v[0] > v[-1]

    def test_batched_shapes(self):
        n = 8
        asp = A.AsyncSpec(buffer_rounds=3, arrival_rate=0.6,
                          rate_heterogeneity=2.0)
        p = np.full((2, n), 1.0 / n)
        c = np.stack([np.ones(n), A.delivery_weight(asp, n)])
        s = np.stack([np.zeros(n), A.expected_staleness(asp, n)])
        v, j = sca_jax.solve_async_batch(p, c, s, [10.0, 10.0], [1.0, 1.0])
        assert v.shape == (2, n) and j.shape == (2,)
        np.testing.assert_allclose(v.sum(axis=1), [8.0, 8.0], atol=1e-6)


# -------------------------------------------------- bound composition

class TestBoundComposition:
    def test_effective_participation_prices_p_c_v(self):
        rng = np.random.default_rng(0)
        n = 8
        p = rng.uniform(0.05, 0.2, n)
        c = rng.uniform(0.3, 1.0, n)
        v = rng.uniform(0.5, 2.0, n)
        v *= n / v.sum()
        eff = async_effective_participation(p, c, v)
        np.testing.assert_allclose(eff, p * c * v * (n / np.sum(c * v)),
                                   rtol=1e-12)
        assert async_bias_sum(p, c, v) == pytest.approx(
            float(np.sum((eff - 1.0 / n) ** 2)))
        # homogeneous delivery is the zero-tilt point: e = p exactly
        np.testing.assert_allclose(
            async_effective_participation(p, np.full(n, 0.4)), p,
            rtol=1e-12)

    def test_theorem1_holds_in_k1_regime(self, setup):
        """K=1 async is independent Bernoulli thinning — the regime
        Theorem 1 models exactly. Measured steady-state optimality error
        must sit below the bound at the async effective levels with the
        analytic delivery variance."""
        task, ds, dep, eta = setup
        n = N_DEVICES
        rounds = 80
        asp = A.AsyncSpec(buffer_rounds=1, arrival_rate=0.7,
                          rate_heterogeneity=2.0)
        res = A.resolve("async", asp, n)
        c = res.delivery_weight_array()
        scale = res.payload_scale_array()
        p = np.full(n, 1.0 / n)
        e = async_effective_participation(p, c)
        zeta = float(task.g_max ** 2 / n ** 2
                     * np.sum(scale ** 2 * c * (1.0 - c)))
        x_all = np.concatenate([d.x for d in ds.devices])
        y_all = np.concatenate([d.y for d in ds.devices])
        w_star = solve_w_star(task, x_all, y_all, iters=1500)
        g = task.device_grads(w_star, np.stack([d.x for d in ds.devices]),
                              np.stack([d.y for d in ds.devices]))
        kappa = float(np.sqrt(np.mean(np.linalg.norm(g, axis=1) ** 2)))
        bound = theorem1_bound(rounds, eta=eta, mu=task.mu, diam=0.0,
                               kappa_sc=kappa, p=e, zeta=zeta)
        tr = FLTrainer(task, ds, dep, eta=eta, mode="async",
                       async_spec=asp)
        log = tr.run(B.IdealFedAvg(), rounds=rounds, trials=2,
                     eval_every=rounds // 4, seed=3, w_star=w_star)
        measured = float(log.opt_error[:, -2:].mean())
        assert measured <= bound["total"] + 1e-6


# --------------------------------------- backend parity + no-op + fast

def _run(setup, agg, *, backend, rng="replay", trainer_kw=None, rounds=ROUNDS,
         trials=TRIALS, seed=5):
    task, ds, dep, eta = setup
    tr = FLTrainer(task, ds, dep, eta=eta, **(trainer_kw or {}))
    return tr.run(agg, rounds=rounds, trials=trials, eval_every=EVAL_EVERY,
                  seed=seed, backend=backend, rng=rng)


def _assert_logs_match(log_np, log_jx):
    assert_parity(log_np, log_jx, n_test=N_TEST)


class TestEngineOracleParity:
    @pytest.mark.parametrize("on_missing", ["zero", "stale"])
    def test_ota_policies(self, setup, on_missing):
        kw = dict(mode="async",
                  async_spec=dataclasses.replace(ASPEC,
                                                 on_missing=on_missing))
        agg = _vanilla(setup)
        _assert_logs_match(_run(setup, agg, backend="numpy", trainer_kw=kw),
                           _run(setup, agg, backend="jax", trainer_kw=kw))

    def test_designed_weights(self, setup):
        """Explicit capped-simplex PS weights flow through both backends
        identically (the 'designed' transport path)."""
        p = np.full(N_DEVICES, 1.0 / N_DEVICES)
        c = A.delivery_weight(ASPEC, N_DEVICES)
        sbar = A.expected_staleness(ASPEC, N_DEVICES)
        v, _ = sca_jax.solve_async_batch(p[None], c[None], sbar[None],
                                         [10.0], [1e3])
        kw = dict(mode="async",
                  async_spec=dataclasses.replace(ASPEC,
                                                 weighting="designed"),
                  async_weights=v[0])
        agg = _vanilla(setup)
        _assert_logs_match(_run(setup, agg, backend="numpy", trainer_kw=kw),
                           _run(setup, agg, backend="jax", trainer_kw=kw))

    def test_composes_with_participation_and_faults(self, setup):
        """Sampling -> async delivery -> fault degradation apply in that
        order in BOTH backends."""
        kw = dict(mode="async", async_spec=ASPEC, clients_per_round=8,
                  participation="channel",
                  fault=FaultSpec(dropout_prob=0.2, on_missing="stale"))
        agg = _vanilla(setup)
        _assert_logs_match(_run(setup, agg, backend="numpy", trainer_kw=kw),
                           _run(setup, agg, backend="jax", trainer_kw=kw))


class TestStrictNoOp:
    def test_sync_is_bit_identical(self, setup):
        """mode='sync' must take the exact pre-async code path — even
        with an AsyncSpec present — bit-identical, not merely close."""
        agg = _vanilla(setup)
        log_off = _run(setup, agg, backend="jax",
                       trainer_kw=dict(mode="sync", async_spec=ASPEC))
        log_plain = _run(setup, agg, backend="jax")
        np.testing.assert_array_equal(log_off.global_loss,
                                      log_plain.global_loss)
        np.testing.assert_array_equal(log_off.accuracy, log_plain.accuracy)

    def test_async_actually_changes_the_run(self, setup):
        agg = _vanilla(setup)
        log_on = _run(setup, agg, backend="jax",
                      trainer_kw=dict(mode="async", async_spec=ASPEC),
                      trials=1)
        log_plain = _run(setup, agg, backend="jax", trials=1)
        assert not np.allclose(log_on.global_loss, log_plain.global_loss,
                               rtol=1e-10)


class TestFastMode:
    def test_counter_only_scheme_bit_identical(self, setup):
        """IdealFedAvg + async consumes ONLY the counter-based ARRIVAL
        stream, which replay and fast share — trajectories must match
        exactly."""
        kw = dict(mode="async", async_spec=ASPEC)
        log_r = _run(setup, B.IdealFedAvg(), backend="jax", rng="replay",
                     trainer_kw=kw)
        log_f = _run(setup, B.IdealFedAvg(), backend="jax", rng="fast",
                     trainer_kw=kw)
        np.testing.assert_array_equal(log_r.global_loss, log_f.global_loss)
        np.testing.assert_array_equal(log_r.accuracy, log_f.accuracy)

    def test_statistical_equivalence_with_async(self, setup):
        """With fading + AWGN re-keyed by fast mode and async on, the
        mean trajectories agree within 4x Monte-Carlo stderr."""
        kw = dict(mode="async", async_spec=ASPEC)
        agg = _vanilla(setup)
        log_r = _run(setup, agg, backend="jax", rng="replay",
                     trainer_kw=kw, trials=12, rounds=30)
        log_f = _run(setup, agg, backend="jax", rng="fast",
                     trainer_kw=kw, trials=12, rounds=30)
        lr, lf = log_r.global_loss, log_f.global_loss
        gap = np.abs(lr.mean(axis=0) - lf.mean(axis=0))
        stderr = np.sqrt(lr.var(axis=0, ddof=1) / lr.shape[0]
                         + lf.var(axis=0, ddof=1) / lf.shape[0])
        assert np.all(gap <= 4.0 * stderr + 1e-7), (gap, stderr)


# ---------------------------------------------------- scenario plumbing

class TestScenarioAxes:
    def test_axes_change_spec_hash(self):
        from repro.api.results import SCHEMA_VERSION
        from repro.api.scenarios import sweep_async

        assert SCHEMA_VERSION == 7
        base = sweep_async(quick=True).base
        h0 = base.spec_hash()
        assert base.override("async_.buffer_rounds", 7).spec_hash() != h0
        assert base.override("async_.staleness_discount",
                             0.5).spec_hash() != h0
        assert base.override("run.mode", "sync").spec_hash() != h0

    def test_mode_validation(self):
        from repro.api.spec import RunSpec

        with pytest.raises(ValueError, match="run.mode"):
            RunSpec(mode="semi-async")

    def test_backcompat(self):
        """Pre-v7 spec dicts (no async_/mode fields) still load, with
        the async layer strictly off."""
        from repro.api.spec import RunSpec, ScenarioSpec

        r = RunSpec(**{"rounds": 8, "trials": 1, "etas": (1.0,)})
        assert r.mode == "sync"
        d = ScenarioSpec().to_dict()
        del d["async_"]
        del d["run"]["mode"]
        sc = ScenarioSpec.from_dict(d)
        assert sc == ScenarioSpec()
        assert sc.run.mode == "sync" and sc.async_ == A.AsyncSpec()
