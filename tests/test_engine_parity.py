"""NumPy-trainer vs JAX-engine parity: same seed -> same trajectories.

The engine (fl/engine.py) replays the NumPy trainer's random streams —
fading, PS AWGN, counter-based quantization dither, selection draws — so
the f32 engine must track the f64 oracle per eval point on loss,
accuracy, opt-error, and wall-clock within the documented f32 tolerances
of ``repro.fl.parity``, for EVERY scheme in ``core.baselines`` (the full
Sec. V suite, ``test_full_suite``). This is the contract that lets
``FLTrainer.run(backend="auto")`` route through the engine.
"""
import numpy as np
import pytest

from repro.core import baselines as B
from repro.core import digital_design, ota_design
from repro.core.bounds import ObjectiveWeights
from repro.core.channel import WirelessConfig, make_deployment
from repro.data.loader import FLDataset
from repro.data.partition import partition_by_class
from repro.data.synthetic import SyntheticSpec, make_classification_dataset
from repro.fl.engine import FLEngine, as_functional
from repro.fl.parity import WALL_RTOL, assert_parity
from repro.fl.tasks import SoftmaxRegressionTask
from repro.fl.trainer import FLTrainer, solve_w_star

N_DEVICES = 10
ROUNDS = 40
TRIALS = 2
EVAL_EVERY = 10
N_TEST = 10 * 30     # 10 classes x n_test_per_class
# engine vs engine: two compiled programs of the same f32 path
TOL = dict(rtol=1e-5, atol=1e-5)


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
    x_all = np.concatenate([d.x for d in ds.devices])
    y_all = np.concatenate([d.y for d in ds.devices])
    w_star = solve_w_star(task, x_all, y_all, iters=600)
    return task, ds, dep, eta, w_star


@pytest.fixture(scope="module")
def ota_params(setup):
    task, ds, dep, eta, _ = setup
    w = ObjectiveWeights.strongly_convex(eta=eta, mu=task.mu, kappa_sc=3.0,
                                         n=N_DEVICES)
    spec = ota_design.OTADesignSpec(
        lambdas=dep.lambdas, dim=task.dim, g_max=task.g_max,
        e_s=dep.cfg.energy_per_symbol, n0=dep.cfg.noise_power, weights=w)
    params, _ = ota_design.design_ota_sca(spec, n_iters=3)
    return params


@pytest.fixture(scope="module")
def dig_params(setup):
    task, ds, dep, eta, _ = setup
    w = ObjectiveWeights.strongly_convex(eta=eta, mu=task.mu, kappa_sc=3.0,
                                         n=N_DEVICES)
    spec = digital_design.DigitalDesignSpec(
        lambdas=dep.lambdas, dim=task.dim, g_max=task.g_max,
        e_s=dep.cfg.energy_per_symbol, n0=dep.cfg.noise_power,
        bandwidth_hz=dep.cfg.bandwidth_hz, t_max_s=0.2, weights=w)
    params, _ = digital_design.design_digital_sca(spec, n_iters=2)
    return params


def _assert_logs_match(log_np, log_jx):
    assert_parity(log_np, log_jx, n_test=N_TEST)


def _run_both(setup, agg, w_star=None):
    task, ds, dep, eta, _ = setup
    tr = FLTrainer(task, ds, dep, eta=eta)
    log_np = tr.run(agg, rounds=ROUNDS, trials=TRIALS, eval_every=EVAL_EVERY,
                    seed=5, w_star=w_star, backend="numpy")
    log_jx = tr.run(agg, rounds=ROUNDS, trials=TRIALS, eval_every=EVAL_EVERY,
                    seed=5, w_star=w_star, backend="jax")
    return log_np, log_jx


def _cfg_args(setup):
    task, _, dep, _, _ = setup
    return (task.dim, task.g_max, dep.cfg.energy_per_symbol,
            dep.cfg.noise_power)


MB_ROUNDS = 20          # mini-batch parity horizon (small T, per the suite)
MB_BATCH = 32           # of 100 samples/device


#: name -> factory(setup) covering the 8 schemes ported in the full-suite
#: engine refactor (the original 6 keep their dedicated tests below)
SCHEME_FACTORIES = {
    "opc_ota_fl": lambda s: B.OPCOTAFL(*_cfg_args(s)),
    "bbfl_interior": lambda s: B.BBFLInterior(s[2], *_cfg_args(s)),
    "bbfl_alternative": lambda s: B.BBFLAlternative(s[2], *_cfg_args(s)),
    "best_channel": lambda s: B.BestChannel(
        s[2], *_cfg_args(s), s[2].cfg.bandwidth_hz),
    "best_channel_norm": lambda s: B.BestChannelNorm(
        s[2], *_cfg_args(s), s[2].cfg.bandwidth_hz),
    "prop_fairness": lambda s: B.PropFairness(
        s[2], *_cfg_args(s), s[2].cfg.bandwidth_hz),
    "uqos": lambda s: B.UQOS(s[2], *_cfg_args(s), s[2].cfg.bandwidth_hz),
    "qml": lambda s: B.QML(s[2], *_cfg_args(s), s[2].cfg.bandwidth_hz),
    "fedtoe": lambda s: B.FedTOE(s[2], *_cfg_args(s), s[2].cfg.bandwidth_hz),
}


#: name -> factory(setup, ota_params, dig_params): EVERY scheme registered
#: in the engine's port routing table (the designed Proposed* schemes need
#: the module-scoped design fixtures, hence the wider signature)
ALL_SCHEME_FACTORIES = dict(
    ideal_fedavg=lambda s, op, dp: B.IdealFedAvg(),
    proposed_ota=lambda s, op, dp: B.ProposedOTA(op),
    vanilla_ota=lambda s, op, dp: B.VanillaOTA(*_cfg_args(s)),
    opc_ota_comp=lambda s, op, dp: B.OPCOTAComp(*_cfg_args(s)),
    lcpc_ota_comp=lambda s, op, dp: B.LCPCOTAComp(s[2], *_cfg_args(s)),
    proposed_digital=lambda s, op, dp: B.ProposedDigital(dp),
    **{k: (lambda f: lambda s, op, dp: f(s))(f)
       for k, f in SCHEME_FACTORIES.items()},
)


class _UnportedAggregator(B.Aggregator):
    """A scheme with no registered JAX port (tests the NumPy fallback)."""

    name = "unported"

    def round(self, grads, h, t, rng, dither=None):
        g = np.mean(np.stack([np.asarray(g) for g in grads]), axis=0)
        return B.RoundResult(g, 0.0, np.ones(len(grads)), {})


class TestTrajectoryParity:
    def test_ideal_fedavg(self, setup):
        _assert_logs_match(*_run_both(setup, B.IdealFedAvg()))

    def test_proposed_ota(self, setup, ota_params):
        _, _, dep, eta, w_star = setup
        log_np, log_jx = _run_both(setup, B.ProposedOTA(ota_params),
                                   w_star=w_star)
        _assert_logs_match(log_np, log_jx)
        assert log_jx.opt_error is not None

    def test_vanilla_ota(self, setup):
        task, _, dep, _, w_star = setup
        agg = B.VanillaOTA(task.dim, task.g_max, dep.cfg.energy_per_symbol,
                           dep.cfg.noise_power)
        _assert_logs_match(*_run_both(setup, agg, w_star=w_star))

    def test_opc_ota_comp(self, setup):
        task, _, dep, _, _ = setup
        agg = B.OPCOTAComp(task.dim, task.g_max, dep.cfg.energy_per_symbol,
                           dep.cfg.noise_power)
        _assert_logs_match(*_run_both(setup, agg))

    def test_lcpc_ota_comp(self, setup):
        task, _, dep, _, _ = setup
        agg = B.LCPCOTAComp(dep, task.dim, task.g_max,
                            dep.cfg.energy_per_symbol, dep.cfg.noise_power)
        _assert_logs_match(*_run_both(setup, agg))

    def test_proposed_digital(self, setup, dig_params):
        _, _, _, _, w_star = setup
        log_np, log_jx = _run_both(setup, B.ProposedDigital(dig_params),
                                   w_star=w_star)
        _assert_logs_match(log_np, log_jx)
        # digital wall-clock is the realized TDMA latency, not d/B: it must
        # vary with participation yet match across backends (checked above)
        assert np.all(np.diff(np.asarray(log_jx.wall_time_s)) > 0)

    @pytest.mark.parametrize("scheme", sorted(SCHEME_FACTORIES))
    def test_full_suite(self, setup, scheme):
        """Every remaining Sec. V baseline: trajectory parity through the
        jittable selection / bit-allocation / RNG-replay machinery."""
        _assert_logs_match(*_run_both(setup, SCHEME_FACTORIES[scheme](setup)))

    def test_mlp_task_parity(self, setup):
        """Non-convex MLPTask (the fig3 path) agrees across backends for
        both an OTA and a digital selection scheme."""
        from repro.fl.tasks import MLPTask

        _, ds, dep, _, _ = setup
        task = MLPTask(n_features=784, hidden=8, mu_nc=0.01, g_max=20.0)
        tr = FLTrainer(task, ds, dep, eta=0.05)
        for agg in (B.VanillaOTA(task.dim, task.g_max,
                                 dep.cfg.energy_per_symbol,
                                 dep.cfg.noise_power),
                    B.BestChannel(dep, task.dim, task.g_max,
                                  dep.cfg.energy_per_symbol,
                                  dep.cfg.noise_power,
                                  dep.cfg.bandwidth_hz)):
            log_np = tr.run(agg, rounds=10, trials=1, eval_every=5, seed=3,
                            backend="numpy")
            log_jx = tr.run(agg, rounds=10, trials=1, eval_every=5, seed=3,
                            backend="jax")
            _assert_logs_match(log_np, log_jx)


class TestMiniBatchParity:
    """SGD mini-batch runs through the engine: counter-based batch indices
    (threefry on seed/trial/round/device) are regenerated inside the scan
    and gathered through the task's device_grads_at path — the exact program
    the NumPy oracle runs, so trajectories match for every registered
    scheme."""

    @pytest.mark.parametrize("scheme", sorted(ALL_SCHEME_FACTORIES))
    def test_minibatch_full_suite(self, setup, ota_params, dig_params,
                                  scheme):
        task, ds, dep, eta, _ = setup
        agg = ALL_SCHEME_FACTORIES[scheme](setup, ota_params, dig_params)
        tr = FLTrainer(task, ds, dep, eta=eta, batch_size=MB_BATCH)
        log_np = tr.run(agg, rounds=MB_ROUNDS, trials=TRIALS,
                        eval_every=EVAL_EVERY, seed=5, backend="numpy")
        log_jx = tr.run(agg, rounds=MB_ROUNDS, trials=TRIALS,
                        eval_every=EVAL_EVERY, seed=5, backend="jax")
        _assert_logs_match(log_np, log_jx)

    def test_minibatch_actually_subsamples(self, setup):
        """A mini-batch run must differ from the full-batch trajectory
        (guards against the sampler silently returning the full dataset)."""
        task, ds, dep, eta, _ = setup
        agg = B.IdealFedAvg()
        log_mb = FLTrainer(task, ds, dep, eta=eta, batch_size=MB_BATCH).run(
            agg, rounds=MB_ROUNDS, trials=1, eval_every=EVAL_EVERY, seed=5,
            backend="jax")
        log_fb = FLTrainer(task, ds, dep, eta=eta).run(
            agg, rounds=MB_ROUNDS, trials=1, eval_every=EVAL_EVERY, seed=5,
            backend="jax")
        assert not np.allclose(log_mb.global_loss[:, -1],
                               log_fb.global_loss[:, -1], rtol=1e-12)

    def test_batch_size_covering_dataset_is_full_batch(self, setup):
        """batch_size >= |D_m| degrades to the full-batch path in both
        backends (DeviceDataset.batch semantics) — and stays in parity."""
        task, ds, dep, eta, _ = setup
        agg = B.IdealFedAvg()
        tr = FLTrainer(task, ds, dep, eta=eta, batch_size=10 ** 6)
        log_np = tr.run(agg, rounds=MB_ROUNDS, trials=1,
                        eval_every=EVAL_EVERY, seed=5, backend="numpy")
        log_jx = tr.run(agg, rounds=MB_ROUNDS, trials=1,
                        eval_every=EVAL_EVERY, seed=5, backend="jax")
        _assert_logs_match(log_np, log_jx)
        log_fb = FLTrainer(task, ds, dep, eta=eta).run(
            agg, rounds=MB_ROUNDS, trials=1, eval_every=EVAL_EVERY, seed=5,
            backend="jax")
        np.testing.assert_array_equal(log_jx.global_loss,
                                      log_fb.global_loss)

    def test_auto_routes_minibatch_through_engine(self, setup):
        task, ds, dep, eta, _ = setup
        tr = FLTrainer(task, ds, dep, eta=eta, batch_size=MB_BATCH)
        tr.run(B.IdealFedAvg(), rounds=4, trials=1, eval_every=2, seed=0)
        assert tr._engine is not None
        assert tr._engine.batch_size == MB_BATCH


class TestTimeBudgetParity:
    """Per-round latency budgets run in-scan: cumulative wall-clock in the
    scan carry, a freeze mask past exhaustion, and eval slots reporting the
    last *live* state — same freeze round and frozen values as the trainer's
    break-and-copy loop."""

    def _run_budget_both(self, setup, agg, budget, *, batch_size=None,
                         rounds=12, eval_every=4):
        task, ds, dep, eta, _ = setup
        tr = FLTrainer(task, ds, dep, eta=eta, batch_size=batch_size)
        log_np = tr.run(agg, rounds=rounds, trials=TRIALS,
                        eval_every=eval_every, seed=0, time_budget_s=budget,
                        backend="numpy")
        log_jx = tr.run(agg, rounds=rounds, trials=TRIALS,
                        eval_every=eval_every, seed=0, time_budget_s=budget,
                        backend="jax")
        return log_np, log_jx

    def test_budget_freeze_parity_ota(self, setup):
        """Budget trips between eval grid points: identical freeze round
        (wall-clock pinned at the same exhaustion time) and frozen evals."""
        task, _, dep, _, _ = setup
        agg = B.VanillaOTA(*_cfg_args(setup))
        per_round = task.dim / dep.cfg.bandwidth_hz
        log_np, log_jx = self._run_budget_both(setup, agg, 5.5 * per_round)
        _assert_logs_match(log_np, log_jx)
        # the budget (airtime for 5.5 rounds) froze after round 6: slots at
        # t=8,12 replicate the t=4 eval, wall pinned at 6 rounds of airtime
        assert np.all(log_jx.global_loss[:, 2:]
                      == log_jx.global_loss[:, 1:2])
        np.testing.assert_allclose(np.asarray(log_jx.wall_time_s)[2:],
                                   6 * per_round, rtol=WALL_RTOL)
        np.testing.assert_allclose(np.asarray(log_np.wall_time_s)[2:],
                                   6 * per_round, rtol=1e-12)

    def test_budget_freeze_parity_digital(self, setup, dig_params):
        """Digital schemes spend *realized* TDMA latency: the freeze round
        is data-dependent, and both backends must agree on it."""
        log_np, log_jx = self._run_budget_both(
            setup, B.ProposedDigital(dig_params), 0.05, rounds=16)
        _assert_logs_match(log_np, log_jx)

    def test_budget_with_minibatch_combined(self, setup):
        """The two new engine paths compose: SGD mini-batches under a
        latency budget stay in parity."""
        task, _, dep, _, _ = setup
        agg = B.VanillaOTA(*_cfg_args(setup))
        per_round = task.dim / dep.cfg.bandwidth_hz
        log_np, log_jx = self._run_budget_both(
            setup, agg, 5.5 * per_round, batch_size=MB_BATCH)
        _assert_logs_match(log_np, log_jx)

    def test_auto_routes_budget_through_engine(self, setup):
        task, ds, dep, eta, _ = setup
        tr = FLTrainer(task, ds, dep, eta=eta)
        tr.run(B.IdealFedAvg(), rounds=4, trials=1, eval_every=2, seed=0,
               time_budget_s=1e9)
        assert tr._engine is not None


class TestUnequalSizesParity:
    """Unequal-sized device datasets run natively in the engine: devices
    are zero-padded to n_max and per-device ragged batch indices — keyed
    on each device's *own* size — are regenerated in-scan, so the draws
    are bit-identical to the oracle's per-device ``batch_indices_np``
    loop and never touch the padding rows. This lifts the last
    engine-dispatch NumPy fallback for strictly mini-batched runs."""

    UNEQ_BATCH = 16

    @pytest.fixture(scope="class")
    def unequal(self, setup):
        from repro.data.loader import DeviceDataset

        task, ds, dep, eta, w_star = setup
        # sizes 100, 93, ..., 37 — all distinct, all > UNEQ_BATCH
        devs = [DeviceDataset(d.x[:100 - 7 * m], d.y[:100 - 7 * m])
                for m, d in enumerate(ds.devices)]
        ds_u = FLDataset(devs, ds.x_test, ds.y_test)
        assert len({len(d) for d in ds_u.devices}) == len(ds_u.devices)
        return task, ds_u, dep, eta, w_star

    @pytest.mark.parametrize("scheme",
                             ["ideal_fedavg", "vanilla_ota", "uqos"])
    def test_unequal_parity(self, unequal, scheme):
        """OTA noise, digital selection+dither, and the noiseless ideal
        path all agree with the oracle on ragged device data."""
        task, ds_u, dep, eta, _ = unequal
        agg = ALL_SCHEME_FACTORIES[scheme](unequal, None, None)
        tr = FLTrainer(task, ds_u, dep, eta=eta, batch_size=self.UNEQ_BATCH)
        log_np = tr.run(agg, rounds=MB_ROUNDS, trials=TRIALS,
                        eval_every=EVAL_EVERY, seed=5, backend="numpy")
        log_jx = tr.run(agg, rounds=MB_ROUNDS, trials=TRIALS,
                        eval_every=EVAL_EVERY, seed=5, backend="jax")
        _assert_logs_match(log_np, log_jx)

    def test_auto_routes_unequal_through_engine(self, unequal):
        task, ds_u, dep, eta, _ = unequal
        tr = FLTrainer(task, ds_u, dep, eta=eta, batch_size=self.UNEQ_BATCH)
        tr.run(B.IdealFedAvg(), rounds=4, trials=1, eval_every=2, seed=0)
        assert tr._engine is not None
        assert tr._engine.device_sizes == tuple(
            len(d) for d in ds_u.devices)

    def test_fast_mode_runs_on_ragged_data(self, unequal):
        """rng='fast' composes with the ragged path (the batch stream is
        already counter-based, so only fading/noise streams change)."""
        task, ds_u, dep, eta, _ = unequal
        agg = B.VanillaOTA(task.dim, task.g_max, dep.cfg.energy_per_symbol,
                           dep.cfg.noise_power)
        tr = FLTrainer(task, ds_u, dep, eta=eta, batch_size=self.UNEQ_BATCH)
        log = tr.run(agg, rounds=8, trials=1, eval_every=4, seed=3,
                     backend="jax", rng="fast")
        assert np.all(np.isfinite(log.global_loss))

    def test_engine_requires_batch_size_on_unequal(self, unequal):
        task, ds_u, dep, eta, _ = unequal
        with pytest.raises(ValueError, match="mini-batch size"):
            FLEngine(task, ds_u, dep, eta)

    @pytest.mark.parametrize("scheme",
                             ["ideal_fedavg", "vanilla_ota", "uqos"])
    def test_mixed_regime_parity(self, unequal, scheme):
        """batch_size >= min |D_m| mixes full- and mini-batch devices.
        Covered devices take weighted full-data gradients (1/n_m on real
        rows, 0 on the clipped duplicates), uncovered ones the exact
        counter-based draw — the oracle's per-device loop semantics, so
        both backends stay in the standard parity tolerance."""
        task, ds_u, dep, eta, _ = unequal
        agg = ALL_SCHEME_FACTORIES[scheme](unequal, None, None)
        tr = FLTrainer(task, ds_u, dep, eta=eta, batch_size=50)
        log_np = tr.run(agg, rounds=MB_ROUNDS, trials=TRIALS,
                        eval_every=EVAL_EVERY, seed=5, backend="numpy")
        log_jx = tr.run(agg, rounds=MB_ROUNDS, trials=TRIALS,
                        eval_every=EVAL_EVERY, seed=5, backend="jax")
        _assert_logs_match(log_np, log_jx)

    def test_mixed_regime_routes_to_engine(self, unequal):
        """The mixed regime is the last regime that used to fall back to
        the NumPy loop — auto must now route it through the engine."""
        task, ds_u, dep, eta, _ = unequal
        tr = FLTrainer(task, ds_u, dep, eta=eta, batch_size=50)
        log = tr.run(B.IdealFedAvg(), rounds=4, trials=1, eval_every=2,
                     seed=0)
        assert tr._engine is not None
        assert np.all(np.isfinite(log.global_loss))

    def test_mixed_regime_all_devices_covered_parity(self, unequal):
        """batch_size >= max |D_m|: every device runs full-batch through
        the weighted path, with no batch draw consumed anywhere."""
        task, ds_u, dep, eta, _ = unequal
        agg = B.VanillaOTA(task.dim, task.g_max, dep.cfg.energy_per_symbol,
                           dep.cfg.noise_power)
        tr = FLTrainer(task, ds_u, dep, eta=eta, batch_size=200)
        log_np = tr.run(agg, rounds=MB_ROUNDS, trials=TRIALS,
                        eval_every=EVAL_EVERY, seed=5, backend="numpy")
        log_jx = tr.run(agg, rounds=MB_ROUNDS, trials=TRIALS,
                        eval_every=EVAL_EVERY, seed=5, backend="jax")
        _assert_logs_match(log_np, log_jx)


class TestGreedyBitAlloc:
    def test_matches_numpy_oracle(self, setup):
        """Jittable greedy allocator (f32, the engine's precision) ==
        FedTOE._alloc_bits (f64) on random scheduled sets, including
        budget-deferral and r_max saturation."""
        import jax.numpy as jnp

        from repro.core.digital import greedy_bit_alloc_jax

        task, _, dep, _, _ = setup
        cfg = dep.cfg
        rng = np.random.default_rng(42)
        configs = [
            dict(t_budget_s=0.22),            # paper default
            dict(t_budget_s=0.04),            # tight: 1-bit deferrals
            dict(t_budget_s=5.0, r_max=6),    # loose: r_max saturation
        ]
        for kw in configs:
            agg = B.FedTOE(dep, task.dim, task.g_max,
                           cfg.energy_per_symbol, cfg.noise_power,
                           cfg.bandwidth_hz, k=5, **kw)
            for _ in range(10):
                sel = rng.choice(dep.n_devices, size=agg.k,
                                 replace=False)
                want = agg._alloc_bits(sel)
                bits, in_alloc = greedy_bit_alloc_jax(
                    jnp.asarray(sel), jnp.asarray(agg.rates),
                    dim=task.dim, bandwidth_hz=cfg.bandwidth_hz,
                    t_budget_s=agg.t_budget, r_max=agg.r_max)
                got = {m: int(b) for m, b in
                       enumerate(np.asarray(bits)) if b > 0}
                assert got == want, (kw, sel)
                assert set(np.flatnonzero(np.asarray(in_alloc))) \
                    == set(want)


class TestBackendDispatch:
    def test_auto_uses_engine_for_ported_schemes(self, setup):
        task, ds, dep, eta, _ = setup
        tr = FLTrainer(task, ds, dep, eta=eta)
        tr.run(B.IdealFedAvg(), rounds=4, trials=1, eval_every=2, seed=0)
        assert tr._engine is not None

    def test_every_baseline_scheme_is_ported(self, setup):
        """The routing table covers the paper's whole Sec. V suite — no
        scheme silently drops to the NumPy loop under backend="auto"."""
        task, _, dep, _, _ = setup
        args = (task.dim, task.g_max, dep.cfg.energy_per_symbol,
                dep.cfg.noise_power)
        suite = [B.IdealFedAvg(), B.VanillaOTA(*args), B.OPCOTAComp(*args),
                 B.OPCOTAFL(*args), B.BBFLInterior(dep, *args),
                 B.BBFLAlternative(dep, *args)]
        suite += [f(setup) for f in SCHEME_FACTORIES.values()]
        for agg in suite:
            assert as_functional(agg) is not None, agg.name

    def test_auto_falls_back_for_unported_schemes(self, setup):
        task, ds, dep, eta, _ = setup
        agg = _UnportedAggregator()
        assert as_functional(agg) is None
        tr = FLTrainer(task, ds, dep, eta=eta)
        log = tr.run(agg, rounds=4, trials=1, eval_every=2, seed=0)
        assert tr._engine is None
        assert np.all(np.isfinite(log.global_loss))

    def test_jax_backend_rejects_unsupported(self, setup):
        task, ds, dep, eta, _ = setup
        agg = _UnportedAggregator()
        tr = FLTrainer(task, ds, dep, eta=eta)
        with pytest.raises(ValueError, match="no JAX port"):
            tr.run(agg, rounds=4, trials=1, eval_every=2, backend="jax")
        with pytest.raises(ValueError, match="backend"):
            tr.run(B.IdealFedAvg(), rounds=4, trials=1, eval_every=2,
                   backend="nope")

    def test_engine_rejects_unported_aggregator(self, setup):
        task, ds, dep, eta, _ = setup
        eng = FLEngine(task, ds, dep, eta)
        with pytest.raises(ValueError, match="no JAX port"):
            eng.run(_UnportedAggregator(), rounds=4, trials=1, eval_every=2)

    def test_shard_trials_flag(self, setup):
        """shard_map over the trials axis reproduces the vmap trajectory
        (single-device mesh here; multi-host is the same flag)."""
        task, ds, dep, eta, _ = setup
        agg = B.VanillaOTA(task.dim, task.g_max, dep.cfg.energy_per_symbol,
                           dep.cfg.noise_power)
        eng = FLEngine(task, ds, dep, eta, shard_trials=True)
        log_sh = eng.run(agg, rounds=6, trials=2, eval_every=2, seed=11)
        log_vm = FLEngine(task, ds, dep, eta).run(
            agg, rounds=6, trials=2, eval_every=2, seed=11)
        np.testing.assert_allclose(log_sh.global_loss, log_vm.global_loss,
                                   **TOL)
        np.testing.assert_allclose(np.asarray(log_sh.wall_time_s),
                                   np.asarray(log_vm.wall_time_s), **TOL)

    def test_non_divisible_rounds(self, setup, ota_params):
        """rounds not a multiple of eval_every: evals stop at the last grid
        point in both backends."""
        task, ds, dep, eta, _ = setup
        tr = FLTrainer(task, ds, dep, eta=eta)
        agg = B.ProposedOTA(ota_params)
        log_np = tr.run(agg, rounds=25, trials=1, eval_every=10, seed=7,
                        backend="numpy")
        log_jx = tr.run(agg, rounds=25, trials=1, eval_every=10, seed=7,
                        backend="jax")
        assert list(log_np.rounds) == [0, 10, 20]
        _assert_logs_match(log_np, log_jx)

    def test_shared_aggregator_across_deployments(self, setup):
        """One aggregator instance run through trainers on *different*
        deployments must not reuse a stale compiled runner (latency scale
        is per-deployment): wall-clock must track each bandwidth."""
        import dataclasses

        task, ds, dep, eta, _ = setup
        agg = B.VanillaOTA(task.dim, task.g_max, dep.cfg.energy_per_symbol,
                           dep.cfg.noise_power)
        dep_fast = make_deployment(
            dataclasses.replace(dep.cfg, bandwidth_hz=dep.cfg.bandwidth_hz
                                * 10), seed=1)
        walls = {}
        for name, d in (("slow", dep), ("fast", dep_fast)):
            tr = FLTrainer(task, ds, d, eta=eta)
            lj = tr.run(agg, rounds=4, trials=1, eval_every=2, seed=1,
                        backend="jax")
            ln = tr.run(agg, rounds=4, trials=1, eval_every=2, seed=1,
                        backend="numpy")
            np.testing.assert_allclose(np.asarray(lj.wall_time_s),
                                       np.asarray(ln.wall_time_s),
                                       rtol=WALL_RTOL)
            walls[name] = (np.asarray(lj.wall_time_s)[-1],
                           np.asarray(ln.wall_time_s)[-1])
        # the engine's latencies are f32, the oracle's f64
        np.testing.assert_allclose(walls["fast"][0], walls["slow"][0] / 10,
                                   rtol=WALL_RTOL)
        np.testing.assert_allclose(walls["fast"][1], walls["slow"][1] / 10,
                                   rtol=1e-12)

    def test_trainer_eta_mutation_rebuilds_engine(self, setup):
        """Mutating trainer.eta after a run must be honored by the JAX
        backend too (the engine is rebuilt, not served stale)."""
        task, ds, dep, eta, _ = setup
        tr = FLTrainer(task, ds, dep, eta=eta)
        tr.run(B.IdealFedAvg(), rounds=4, trials=1, eval_every=2, seed=1)
        tr.eta = eta / 10
        lj = tr.run(B.IdealFedAvg(), rounds=4, trials=1, eval_every=2,
                    seed=1, backend="jax")
        ln = tr.run(B.IdealFedAvg(), rounds=4, trials=1, eval_every=2,
                    seed=1, backend="numpy")
        assert_parity(ln, lj, n_test=N_TEST)

    def test_eval_every_exceeds_rounds(self, setup):
        """rounds < eval_every: a single t=0 eval, zero scan segments (the
        empty fading-batch regression)."""
        task, ds, dep, eta, _ = setup
        tr = FLTrainer(task, ds, dep, eta=eta)
        log_np = tr.run(B.IdealFedAvg(), rounds=3, trials=1, eval_every=10,
                        seed=7, backend="numpy")
        log_jx = tr.run(B.IdealFedAvg(), rounds=3, trials=1, eval_every=10,
                        seed=7, backend="jax")
        assert list(log_jx.rounds) == [0]
        _assert_logs_match(log_np, log_jx)
