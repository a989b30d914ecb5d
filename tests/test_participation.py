"""Partial-participation contracts (``core.participation`` + both backends).

The sampling layer's guarantees, mirroring the fault-layer suite:

  * the PARTICIPATE stream is counter-based and bit-shared: the NumPy
    helper and the JAX in-scan block produce identical (N,) uniforms,
    distinct from every other stream's draws,
  * ``resolve``/``capped_proportional`` validate and normalize the
    (clients, policy, probs) knobs identically for both backends,
  * engine-vs-oracle parity holds with sampling on (uniform / channel /
    designed), alone and composed with the fault layer,
  * ``clients_per_round=None`` is a strict no-op (bit-identical to a
    trainer that never heard of participation),
  * ``rng="fast"`` stays statistically equivalent to replay with
    sampling on — and bit-identical for a scheme that consumes only
    counter-based streams,
  * the co-design solver (``core.sca_jax.solve_participation_batch``)
    returns feasible capped-simplex points that beat uniform on its own
    bound-shaped objective for heterogeneous survival rates,
  * ``run.clients_per_round`` / ``run.participation`` are sweepable axes
    that change the cell hash (schema v6).
"""
import numpy as np
import pytest

from repro.core import baselines as B
from repro.core import participation as P
from repro.core import rngstream, sca_jax
from repro.core.bounds import effective_participation
from repro.core.channel import WirelessConfig, make_deployment
from repro.core.faults import FaultSpec
from repro.data.loader import FLDataset
from repro.data.partition import partition_by_class
from repro.data.synthetic import SyntheticSpec, make_classification_dataset
from repro.fl.tasks import SoftmaxRegressionTask
from repro.fl.parity import assert_parity
from repro.fl.trainer import FLTrainer

N_DEVICES = 10
ROUNDS = 20
TRIALS = 2
EVAL_EVERY = 5
CLIENTS = 6
N_TEST = 10 * 30     # 10 classes x n_test_per_class


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


# ------------------------------------------------- PARTICIPATE stream

class TestStream:
    @pytest.mark.parametrize("seed,trial,t", [(0, 0, 0), (5, 1, 7),
                                              (123, 3, 999)])
    def test_np_matches_jax_bitwise(self, seed, trial, t):
        """The NumPy oracle helper and the engine's in-scan block draw the
        SAME threefry counters — identical bits, not just close."""
        u_np = rngstream.participation_block_np(seed, trial, t, 64)
        key = rngstream.participate_base_key(seed, trial)
        u_jx = np.asarray(rngstream.participation_block(key, t, 64))
        assert u_np.dtype == np.float64
        np.testing.assert_array_equal(u_np, u_jx)
        assert np.all((u_np >= 0.0) & (u_np < 1.0))

    def test_distinct_from_other_streams(self):
        """PARTICIPATE is its own tagged stream: same (seed, trial, t)
        counters, different draws than the FAULT block."""
        u_part = rngstream.participation_block_np(5, 1, 7, 64)
        u_fault = rngstream.fault_block_np(5, 1, 7, 64)
        assert not np.array_equal(u_part, u_fault)

    def test_deterministic(self):
        a = rngstream.participation_block_np(9, 2, 13, 32)
        b = rngstream.participation_block_np(9, 2, 13, 32)
        np.testing.assert_array_equal(a, b)

    def test_bernoulli_rate(self):
        """chi = (u < pi) hits the target inclusion rate to 4 sigma."""
        pi = 0.35
        rounds, n = 400, 64
        hits = sum(
            float(np.sum(rngstream.participation_block_np(2, 0, t, n) < pi))
            for t in range(rounds))
        mean = hits / (rounds * n)
        sigma = np.sqrt(pi * (1 - pi) / (rounds * n))
        assert abs(mean - pi) <= 4.0 * sigma

    def test_key_cache_is_bounded_and_stable(self):
        """The NumPy helper's base-key cache is a bounded LRU: flooding it
        with distinct (seed, trial) pairs never grows it past the cap,
        and an evicted key recomputes to the identical block."""
        cache = rngstream._PARTICIPATE_KEY_CACHE
        before = rngstream.participation_block_np(7, 0, 3, 16)
        for s in range(rngstream._KEY_CACHE_MAX + 50):
            rngstream.participation_block_np(10_000 + s, 0, 0, 4)
        assert len(cache) <= rngstream._KEY_CACHE_MAX
        after = rngstream.participation_block_np(7, 0, 3, 16)
        np.testing.assert_array_equal(before, after)


# ------------------------------------------- resolve / capped simplex

class TestResolve:
    def test_none_is_none(self):
        assert P.resolve(None, n_devices=8) is None

    def test_probs_without_clients_rejected(self):
        with pytest.raises(ValueError, match="clients_per_round is None"):
            P.resolve(None, probs=np.full(8, 0.5), n_devices=8)

    def test_uniform(self):
        part = P.resolve(4, "uniform", n_devices=8)
        assert part.policy == "uniform" and part.clients == 4
        assert part.scale == 2.0
        np.testing.assert_allclose(part.probs_array(), 0.5)
        assert {part: "hashable"}[part] == "hashable"

    def test_channel_needs_lambdas(self):
        with pytest.raises(ValueError, match="lambdas"):
            P.resolve(4, "channel", n_devices=8)

    def test_channel_capped_simplex(self):
        lam = np.array([1.0, 1.0, 1e3, 1e-3, 2.0, 0.5, 1.0, 4.0])
        part = P.resolve(4, "channel", n_devices=8, lambdas=lam)
        pi = part.probs_array()
        assert abs(pi.sum() - 4.0) < 1e-9
        assert np.all(pi <= 1.0) and np.all(pi > 0.0)
        assert pi[2] == 1.0          # the dominant channel saturates

    def test_designed_needs_probs(self):
        with pytest.raises(ValueError, match="explicit participation_probs"):
            P.resolve(4, "designed", n_devices=8)

    def test_explicit_probs_validation(self):
        ok = np.full(8, 0.5)
        part = P.resolve(4, "designed", probs=ok, n_devices=8)
        np.testing.assert_allclose(part.probs_array(), ok)
        with pytest.raises(ValueError, match="shape"):
            P.resolve(4, "designed", probs=np.full(7, 0.5), n_devices=8)
        with pytest.raises(ValueError, match=r"\(0, 1\]"):
            bad = ok.copy(); bad[0] = 1.5
            P.resolve(4, "designed", probs=bad, n_devices=8)
        with pytest.raises(ValueError, match="sum"):
            P.resolve(4, "designed", probs=np.full(8, 0.4), n_devices=8)

    @pytest.mark.parametrize("bad_s", [0, -1, 9])
    def test_clients_out_of_range(self, bad_s):
        with pytest.raises(ValueError, match="clients_per_round"):
            P.resolve(bad_s, n_devices=8)

    def test_bad_policy(self):
        with pytest.raises(ValueError, match="participation must be"):
            P.resolve(4, "importance", n_devices=8)

    def test_full_cohort(self):
        part = P.resolve(8, "uniform", n_devices=8)
        np.testing.assert_allclose(part.probs_array(), 1.0)
        assert part.scale == 1.0

    def test_capped_proportional_properties(self):
        w = np.array([0.1, 10.0, 1.0, 1.0, 5.0, 0.01])
        pi = P.capped_proportional(w, 3)
        assert abs(pi.sum() - 3.0) < 1e-9
        assert np.all(pi <= 1.0) and pi[1] == 1.0
        np.testing.assert_allclose(P.capped_proportional(w, 6), 1.0)
        with pytest.raises(ValueError, match="positive participation"):
            P.capped_proportional(np.array([1.0, 0.0, 0.0]), 2)

    @pytest.mark.parametrize("policy", ["loss", "datasize"])
    def test_weighted_policies_need_weights(self, policy):
        with pytest.raises(ValueError, match="per-device weights"):
            P.resolve(4, policy, n_devices=8)

    def test_weighted_policy_capped_simplex(self):
        w = np.array([3.0, 1.0, 1.0, 40.0, 2.0, 1.0, 1.0, 1.0])
        part = P.resolve(4, "loss", n_devices=8, weights=w)
        pi = part.probs_array()
        assert part.policy == "loss"
        assert abs(pi.sum() - 4.0) < 1e-9
        assert np.all(pi <= 1.0) and np.all(pi > 0.0)
        assert pi[3] == 1.0          # the dominant weight saturates
        np.testing.assert_array_equal(
            pi, P.resolve(4, "datasize", n_devices=8,
                          weights=w).probs_array())

    def test_policy_weights_derivation(self, setup):
        """datasize weights are the shard sizes; loss weights are the
        per-device initial losses — deterministic on both backends."""
        task, ds, _, _ = setup
        wd = P.policy_weights("datasize", task, ds)
        np.testing.assert_array_equal(
            wd, [float(len(d)) for d in ds.devices])
        wl = P.policy_weights("loss", task, ds)
        w0 = task.init_params()
        np.testing.assert_array_equal(
            wl, [float(task.global_loss(w0, d.x, d.y))
                 for d in ds.devices])
        assert P.policy_weights("uniform") is None
        with pytest.raises(ValueError, match="task and dataset"):
            P.policy_weights("loss")


# ------------------------------------------------------ co-design solver

class TestSolver:
    def test_feasible_and_beats_uniform(self):
        """Heterogeneous survival: the designed pi is on the capped
        simplex and strictly improves the bound-shaped objective over the
        zero-bias uniform point (evaluated with the same formula)."""
        n, s = 12, 4
        p = np.full(n, 1.0 / n)
        q = np.where(np.arange(n) < 6, 0.95, 0.05)
        wv, wb = 50.0, 1e-3

        def obj(pi):
            e = (n / s) * p * pi * q
            return (wb * np.sum((e - 1.0 / n) ** 2)
                    + wv / np.sum(e) ** 2)

        pi, j = sca_jax.solve_participation_batch(
            p[None], q[None], [s], [wv], [wb])
        pi, j = pi[0], float(j[0])
        assert abs(pi.sum() - s) < 1e-6
        assert np.all(pi <= 1.0 + 1e-12) and np.all(pi > 0.0)
        np.testing.assert_allclose(j, obj(pi), rtol=1e-10)
        assert j < obj(np.full(n, s / n))

    def test_batched_shapes(self):
        n = 8
        p = np.full((3, n), 1.0 / n)
        q = np.stack([np.ones(n), np.linspace(0.1, 1.0, n),
                      np.full(n, 0.5)])
        pi, j = sca_jax.solve_participation_batch(
            p, q, [2, 4, 6], [10.0, 10.0, 10.0], [1.0, 1.0, 1.0])
        assert pi.shape == (3, n) and j.shape == (3,)
        np.testing.assert_allclose(pi.sum(axis=1), [2.0, 4.0, 6.0],
                                   atol=1e-6)


# -------------------------------------------------- bound composition

class TestBoundComposition:
    def test_effective_participation_prices_p_pi_q(self):
        rng = np.random.default_rng(0)
        n, s = 8, 4
        p = rng.uniform(0.05, 0.2, n)
        q = rng.uniform(0.3, 1.0, n)
        pi = P.capped_proportional(rng.uniform(0.5, 2.0, n), s)
        eff = effective_participation(p, q, "zero", pi=pi)
        np.testing.assert_allclose(eff, p * q * pi * (n / pi.sum()),
                                   rtol=1e-12)
        # uniform pi is the zero-bias point: the sampling factor is 1
        uni = np.full(n, s / n)
        np.testing.assert_allclose(
            effective_participation(p, q, "reweight", pi=uni), p,
            rtol=1e-12)


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
    @pytest.mark.parametrize("policy", ["uniform", "channel"])
    def test_ota_policies(self, setup, policy):
        kw = dict(clients_per_round=CLIENTS, participation=policy)
        agg = _vanilla(setup)
        _assert_logs_match(_run(setup, agg, backend="numpy", trainer_kw=kw),
                           _run(setup, agg, backend="jax", trainer_kw=kw))

    def test_designed_probs(self, setup):
        """Arbitrary static capped-simplex probabilities flow through both
        backends identically (the 'designed' transport path)."""
        _, _, dep, _ = setup
        probs = P.capped_proportional(np.sqrt(dep.lambdas), CLIENTS)
        kw = dict(clients_per_round=CLIENTS, participation="designed",
                  participation_probs=probs)
        agg = _vanilla(setup)
        _assert_logs_match(_run(setup, agg, backend="numpy", trainer_kw=kw),
                           _run(setup, agg, backend="jax", trainer_kw=kw))

    @pytest.mark.parametrize("policy", ["loss", "datasize"])
    def test_weighted_policies(self, setup, policy):
        """The trainer/engine derive the loss/datasize sampling weights
        from their own task/dataset — identically on both backends."""
        kw = dict(clients_per_round=CLIENTS, participation=policy)
        agg = _vanilla(setup)
        _assert_logs_match(_run(setup, agg, backend="numpy", trainer_kw=kw),
                           _run(setup, agg, backend="jax", trainer_kw=kw))

    def test_selection_scheme(self, setup):
        """Client sampling composes with a selection-based digital scheme
        (sampling thins the pool the per-round selection draws from)."""
        task, _, dep, _ = setup
        agg = B.UQOS(dep, task.dim, task.g_max, dep.cfg.energy_per_symbol,
                     dep.cfg.noise_power, dep.cfg.bandwidth_hz)
        kw = dict(clients_per_round=CLIENTS)
        _assert_logs_match(_run(setup, agg, backend="numpy", trainer_kw=kw),
                           _run(setup, agg, backend="jax", trainer_kw=kw))

    def test_composes_with_fault_layer(self, setup):
        """Participation x faults: the chi mask applies before the fault
        policy in BOTH backends (p * pi * q ordering)."""
        kw = dict(clients_per_round=CLIENTS,
                  fault=FaultSpec(dropout_prob=0.2, deep_fade_thresh=1e-7,
                                  on_missing="zero"))
        agg = _vanilla(setup)
        _assert_logs_match(_run(setup, agg, backend="numpy", trainer_kw=kw),
                           _run(setup, agg, backend="jax", trainer_kw=kw))


class TestStrictNoOp:
    def test_none_is_bit_identical(self, setup):
        """clients_per_round=None must take the exact pre-participation
        code path — bit-identical, not merely close."""
        agg = _vanilla(setup)
        log_off = _run(setup, agg, backend="jax",
                       trainer_kw=dict(clients_per_round=None))
        log_plain = _run(setup, agg, backend="jax")
        np.testing.assert_array_equal(log_off.global_loss,
                                      log_plain.global_loss)
        np.testing.assert_array_equal(log_off.accuracy, log_plain.accuracy)

    def test_sampling_actually_changes_the_run(self, setup):
        agg = _vanilla(setup)
        log_on = _run(setup, agg, backend="jax",
                      trainer_kw=dict(clients_per_round=CLIENTS), trials=1)
        log_plain = _run(setup, agg, backend="jax", trials=1)
        assert not np.allclose(log_on.global_loss, log_plain.global_loss,
                               rtol=1e-10)


class TestFastMode:
    def test_counter_only_scheme_bit_identical(self, setup):
        """IdealFedAvg + sampling consumes ONLY the counter-based
        PARTICIPATE stream, which replay and fast share — trajectories
        must match exactly."""
        kw = dict(clients_per_round=CLIENTS)
        log_r = _run(setup, B.IdealFedAvg(), backend="jax", rng="replay",
                     trainer_kw=kw)
        log_f = _run(setup, B.IdealFedAvg(), backend="jax", rng="fast",
                     trainer_kw=kw)
        np.testing.assert_array_equal(log_r.global_loss, log_f.global_loss)
        np.testing.assert_array_equal(log_r.accuracy, log_f.accuracy)

    def test_statistical_equivalence_with_sampling(self, setup):
        """With fading + AWGN re-keyed by fast mode and sampling on, the
        mean trajectories agree within 4x Monte-Carlo stderr."""
        kw = dict(clients_per_round=CLIENTS)
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
        from repro.api.scenarios import sweep_participation

        assert SCHEMA_VERSION == 7
        base = sweep_participation(quick=True).base
        h0 = base.spec_hash()
        assert base.override("run.clients_per_round", 4).spec_hash() != h0
        assert base.override("run.participation",
                             "designed").spec_hash() != h0

    def test_runspec_backcompat(self):
        """Pre-v6 payload dicts (no participation fields) still load."""
        from repro.api.spec import RunSpec

        old = {"rounds": 8, "trials": 1, "etas": (1.0,)}
        r = RunSpec(**old)
        assert r.clients_per_round is None
        assert r.participation == "uniform"
