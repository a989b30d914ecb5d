"""Regression tests for FLTrainer's time-budget freeze path.

The freeze must anchor on the last *written* eval slot — never on
uninitialized array slots — and every frozen eval must replicate that
anchor exactly (loss/accuracy/opt-error), with the wall-clock pinned at
the budget-exhaustion time. Since the engine port of mini-batching/time
budgets, both backends implement these semantics (the NumPy loop by
break-and-copy, the engine by an in-scan freeze mask), so the tests run
parametrized over ``backend``.
"""
import numpy as np
import pytest

from repro.core import baselines as B
from repro.core.channel import WirelessConfig, make_deployment
from repro.data.loader import FLDataset
from repro.data.partition import partition_by_class
from repro.data.synthetic import SyntheticSpec, make_classification_dataset
from repro.fl.parity import WALL_RTOL
from repro.fl.tasks import SoftmaxRegressionTask
from repro.fl.trainer import FLTrainer

BACKENDS = ("numpy", "jax")


@pytest.fixture(scope="module")
def setup():
    spec = SyntheticSpec(n_train_per_class=60, n_test_per_class=20,
                         noise_sigma=1.5)
    x_tr, y_tr, x_te, y_te = make_classification_dataset(spec)
    shards = partition_by_class(x_tr, y_tr, 6, 1, 60, seed=3)
    ds = FLDataset.from_shards(shards, x_te, y_te)
    task = SoftmaxRegressionTask(n_features=784, mu=0.01, g_max=20.0)
    dep = make_deployment(WirelessConfig(n_devices=6, seed=1))
    eta = 0.5 / (task.mu + task.smooth_l)
    return task, ds, dep, eta


@pytest.mark.parametrize("backend", BACKENDS)
def test_budget_trips_mid_grid_freezes_last_written(setup, backend):
    """Budget exhausted at a round *between* eval points: the frozen tail
    must equal the last eval actually written, not a stale/unwritten slot."""
    task, ds, dep, eta = setup
    tr = FLTrainer(task, ds, dep, eta=eta)
    # OTA latency is d/B per round; budget for ~1.5 rounds trips at t=2,
    # strictly between the eval grid points 0 and 4 (IdealFedAvg is free,
    # so use a scheme that actually spends airtime)
    agg = B.VanillaOTA(task.dim, task.g_max, dep.cfg.energy_per_symbol,
                       dep.cfg.noise_power)
    per_round = task.dim / dep.cfg.bandwidth_hz
    log = tr.run(agg, rounds=12, trials=2, eval_every=4, seed=0,
                 w_star=np.zeros(task.dim),
                 time_budget_s=1.5 * per_round, backend=backend)
    assert list(log.rounds) == [0, 4, 8, 12]
    for trial in range(2):
        # only the t=0 eval ran; every later slot is frozen to it
        for j in range(1, 4):
            assert log.global_loss[trial, j] == log.global_loss[trial, 0]
            assert log.accuracy[trial, j] == log.accuracy[trial, 0]
            assert log.opt_error[trial, j] == log.opt_error[trial, 0]
    assert np.all(np.isfinite(log.global_loss))
    # frozen wall-clock records when the budget tripped (2 rounds elapsed):
    # the f64 oracle's sum is exact, the engine's is an f32 sum
    np.testing.assert_allclose(np.asarray(log.wall_time_s)[1:],
                               2 * per_round,
                               rtol=1e-12 if backend == "numpy" else WALL_RTOL)


@pytest.mark.parametrize("backend", BACKENDS)
def test_budget_zero_freezes_initial_eval(setup, backend):
    """A zero budget trips immediately after the t=0 eval; all slots must
    equal the initial-model eval (the ei-1 underflow regression — the
    ``ei >= 1`` invariant: the t=0 eval is always written before the first
    budget check, in both backends)."""
    task, ds, dep, eta = setup
    tr = FLTrainer(task, ds, dep, eta=eta)
    log = tr.run(B.IdealFedAvg(), rounds=8, trials=1, eval_every=2, seed=0,
                 time_budget_s=0.0, backend=backend)
    assert np.all(log.global_loss == log.global_loss[:, :1])
    assert np.all(log.accuracy == log.accuracy[:, :1])
    assert np.all(np.asarray(log.wall_time_s) == 0.0)


@pytest.mark.parametrize("backend", BACKENDS)
def test_budget_generous_matches_unbudgeted(setup, backend):
    """A budget that never trips must not change the trajectory."""
    task, ds, dep, eta = setup
    tr = FLTrainer(task, ds, dep, eta=eta)
    log_a = tr.run(B.IdealFedAvg(), rounds=8, trials=1, eval_every=4, seed=3,
                   backend=backend)
    log_b = tr.run(B.IdealFedAvg(), rounds=8, trials=1, eval_every=4, seed=3,
                   time_budget_s=1e9, backend=backend)
    np.testing.assert_array_equal(log_a.global_loss, log_b.global_loss)
    np.testing.assert_array_equal(np.asarray(log_a.wall_time_s),
                                  np.asarray(log_b.wall_time_s))


def test_jax_backend_accepts_budget_and_minibatch(setup):
    """backend="jax" no longer raises for time_budget_s / batch_size — the
    regimes that used to silently fall back to the NumPy loop."""
    task, ds, dep, eta = setup
    agg = B.VanillaOTA(task.dim, task.g_max, dep.cfg.energy_per_symbol,
                       dep.cfg.noise_power)
    per_round = task.dim / dep.cfg.bandwidth_hz
    tr = FLTrainer(task, ds, dep, eta=eta, batch_size=16)
    log = tr.run(agg, rounds=8, trials=1, eval_every=4, seed=0,
                 time_budget_s=3.5 * per_round, backend="jax")
    assert tr._engine is not None and tr._engine.batch_size == 16
    assert np.all(np.isfinite(log.global_loss))
    # budget for 3.5 rounds: t=4 eval live, t=8 frozen to it
    assert log.global_loss[0, 2] == log.global_loss[0, 1]
    assert log.global_loss[0, 1] != log.global_loss[0, 0]
    np.testing.assert_allclose(np.asarray(log.wall_time_s)[-1],
                               4 * per_round, rtol=WALL_RTOL)


def test_engine_budget_freeze_matches_oracle_exactly(setup):
    """Cross-backend: identical freeze round, frozen eval values, and
    pinned wall-clock on a budget that trips mid-run."""
    task, ds, dep, eta = setup
    agg = B.VanillaOTA(task.dim, task.g_max, dep.cfg.energy_per_symbol,
                       dep.cfg.noise_power)
    per_round = task.dim / dep.cfg.bandwidth_hz
    tr = FLTrainer(task, ds, dep, eta=eta)
    logs = {bk: tr.run(agg, rounds=12, trials=2, eval_every=4, seed=1,
                       time_budget_s=6.5 * per_round, backend=bk)
            for bk in BACKENDS}
    np.testing.assert_allclose(logs["jax"].global_loss,
                               logs["numpy"].global_loss,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(logs["jax"].wall_time_s),
                               np.asarray(logs["numpy"].wall_time_s),
                               rtol=1e-5, atol=1e-5)
    # both froze after round 7 (budget = 6.5 rounds of airtime)
    for log in logs.values():
        assert np.all(log.global_loss[:, 2:] == log.global_loss[:, 1:2])
