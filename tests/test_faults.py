"""Wireless fault-injection contracts (``core.faults`` + both backends).

The fault layer's guarantees:

  * the FAULT stream is counter-based threefry in BOTH rng execution
    modes and on BOTH backends — fault realizations are bit-identical
    across ``rng="replay"``/``"fast"`` and numpy/jax,
  * empirical fault rates match the declared probabilities (4-sigma
    gate, mirroring the fast-RNG suite's statistical discipline),
  * each ``on_missing`` policy produces the same trajectory on the JAX
    engine as on the NumPy oracle loop,
  * a disabled ``FaultSpec`` is a strict no-op: trajectories are
    bit-identical to a run with no fault layer at all,
  * the fault knobs are sweepable spec axes that change cell hashes, and
    pre-v5 spec dicts (no "fault" key) still load.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import baselines as B
from repro.core import rngstream
from repro.core.bounds import bias_sum, effective_participation
from repro.core.channel import WirelessConfig, make_deployment
from repro.core.faults import (FaultSpec, effective_lambdas, fault_masks,
                               survival_prob)
from repro.data.loader import FLDataset
from repro.data.partition import partition_by_class
from repro.data.synthetic import SyntheticSpec, make_classification_dataset
from repro.fl.parity import WALL_RTOL, assert_parity
from repro.fl.trainer import FLTrainer

N_DEVICES = 10
N_TEST = 10 * 30     # 10 classes x n_test_per_class


@pytest.fixture(scope="module")
def setup():
    from repro.fl.tasks import SoftmaxRegressionTask

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


def _run(setup, agg, fault, *, backend, rng="replay", trials=2, rounds=12,
         eval_every=4, seed=5, batch_size=None):
    task, ds, dep, eta = setup
    tr = FLTrainer(task, ds, dep, eta=eta, batch_size=batch_size,
                   fault=fault)
    return tr.run(agg, rounds=rounds, trials=trials, eval_every=eval_every,
                  seed=seed, backend=backend, rng=rng)


FULL_FAULT = dict(dropout_prob=0.3, erasure_prob=0.1, deep_fade_thresh=1e-6,
                  straggler_prob=0.2, straggler_mult=2.5)


class TestFaultStream:
    def test_fault_block_np_matches_jax(self):
        """The oracle view is byte-for-byte the jitted stream."""
        for trial in (0, 1):
            for t in (0, 7, 123):
                u_np = rngstream.fault_block_np(5, trial, t, N_DEVICES)
                u_jx = rngstream.fault_block(
                    rngstream.fault_base_key(5, trial), t, N_DEVICES)
                np.testing.assert_array_equal(u_np, np.asarray(u_jx))

    def test_fault_stream_distinct_from_other_streams(self):
        """FAULT_TAG is its own stream — no collision with dither/batch."""
        u = rngstream.fault_block_np(5, 0, 0, N_DEVICES)
        d = rngstream.dither_block_np(5, 0, 0, N_DEVICES, 3)
        assert not np.allclose(u[0][:3], d[0][:3])

    def test_empirical_rates_within_4_sigma(self):
        """Dropout/erasure/straggler rates over many rounds match the
        declared probabilities within 4 standard errors."""
        f = FaultSpec(dropout_prob=0.3, erasure_prob=0.1,
                      straggler_prob=0.2)
        rounds, n = 400, N_DEVICES
        hits = np.zeros(3)
        habs = np.ones(n)        # no fades: isolate the bernoulli draws
        for t in range(rounds):
            u = rngstream.fault_block_np(11, 0, t, n)
            hits[0] += np.sum(u[0] < f.dropout_prob)
            hits[1] += np.sum(u[1] < f.erasure_prob)
            hits[2] += np.sum(u[2] < f.straggler_prob)
            ok, straggler = fault_masks(u, habs, f)
            assert ok.shape == (n,) and straggler.shape == (n,)
        total = rounds * n
        for rate, p in zip(hits / total, (0.3, 0.1, 0.2)):
            sigma = np.sqrt(p * (1 - p) / total)
            assert abs(rate - p) <= 4.0 * sigma, (rate, p)


class TestPolicyParity:
    """Each on_missing policy: JAX engine == NumPy oracle loop."""

    @pytest.mark.parametrize("policy", ["zero", "reweight", "stale"])
    def test_engine_matches_oracle(self, setup, policy):
        f = FaultSpec(on_missing=policy, **FULL_FAULT)
        agg = _vanilla(setup)
        log_np = _run(setup, agg, f, backend="numpy")
        log_jx = _run(setup, agg, f, backend="jax")
        assert_parity(log_np, log_jx, n_test=N_TEST)

    def test_deadline_caps_latency_on_both_backends(self, setup):
        f = FaultSpec(dropout_prob=0.2, straggler_prob=0.3,
                      deadline_s=1e-4, on_missing="zero")
        agg = _vanilla(setup)
        log_np = _run(setup, agg, f, backend="numpy", trials=1)
        log_jx = _run(setup, agg, f, backend="jax", trials=1)
        np.testing.assert_allclose(log_jx.wall_time_s, log_np.wall_time_s,
                                   rtol=WALL_RTOL)
        # every round costs at most the deadline
        assert log_np.wall_time_s[-1] <= 12 * 1e-4 + 1e-12

    def test_stragglers_stretch_rounds_without_deadline(self, setup):
        base = FaultSpec(dropout_prob=0.1, on_missing="zero")
        slow = dataclasses.replace(base, straggler_prob=0.5,
                                   straggler_mult=4.0)
        agg = _vanilla(setup)
        t_base = _run(setup, agg, base, backend="jax",
                      trials=1).wall_time_s[-1]
        t_slow = _run(setup, agg, slow, backend="jax",
                      trials=1).wall_time_s[-1]
        assert t_slow > t_base

    def test_policies_actually_differ(self, setup):
        agg = _vanilla(setup)
        finals = [
            _run(setup, agg,
                 FaultSpec(on_missing=p, **FULL_FAULT),
                 backend="jax", trials=1).global_loss[:, -1].item()
            for p in ("zero", "reweight", "stale")]
        assert len({round(v, 12) for v in finals}) == 3, finals


class TestRngModes:
    def test_fault_stream_bit_identical_replay_vs_fast(self, setup):
        """IdealFedAvg + mini-batch + faults consumes only counter-based
        streams (batch + fault) — trajectories must be exactly equal
        across rng modes, pinning the FAULT stream as mode-invariant."""
        f = FaultSpec(dropout_prob=0.25, on_missing="stale")
        log_r = _run(setup, B.IdealFedAvg(), f, backend="jax",
                     rng="replay", rounds=20, batch_size=32)
        log_f = _run(setup, B.IdealFedAvg(), f, backend="jax",
                     rng="fast", rounds=20, batch_size=32)
        np.testing.assert_array_equal(log_r.global_loss, log_f.global_loss)
        np.testing.assert_array_equal(log_r.accuracy, log_f.accuracy)

    def test_faulted_fast_statistically_equivalent(self, setup):
        """With faults on, fast mode still matches replay within MC error
        (the channel-coupled deep-fade mask sees different fading draws)."""
        f = FaultSpec(on_missing="reweight", **FULL_FAULT)
        agg = _vanilla(setup)
        log_r = _run(setup, agg, f, backend="jax", rng="replay",
                     trials=12, rounds=30, eval_every=10)
        log_f = _run(setup, agg, f, backend="jax", rng="fast",
                     trials=12, rounds=30, eval_every=10)
        lr, lf = log_r.global_loss, log_f.global_loss
        stderr = np.sqrt(lr.var(axis=0, ddof=1) / lr.shape[0]
                         + lf.var(axis=0, ddof=1) / lf.shape[0])
        gap = np.abs(lr.mean(axis=0) - lf.mean(axis=0))
        assert np.all(gap <= 4.0 * stderr + 1e-7), (gap, stderr)


class TestStrictNoOp:
    def test_disabled_fault_is_bit_identical(self, setup):
        agg = _vanilla(setup)
        log_none = _run(setup, agg, None, backend="jax", trials=1)
        log_off = _run(setup, agg, FaultSpec(), backend="jax", trials=1)
        np.testing.assert_array_equal(log_none.global_loss,
                                      log_off.global_loss)
        np.testing.assert_array_equal(log_none.wall_time_s,
                                      log_off.wall_time_s)

    def test_straggler_mult_alone_is_inert(self, setup):
        """straggler_mult without straggler_prob scales nothing."""
        f = FaultSpec(straggler_mult=10.0)
        assert not f.enabled
        agg = _vanilla(setup)
        log_none = _run(setup, agg, None, backend="numpy", trials=1)
        log_off = _run(setup, agg, f, backend="numpy", trials=1)
        np.testing.assert_array_equal(log_none.global_loss,
                                      log_off.global_loss)

    def test_disabled_fault_numpy_oracle(self, setup):
        agg = _vanilla(setup)
        log_none = _run(setup, agg, None, backend="numpy", trials=1)
        log_off = _run(setup, agg, FaultSpec(), backend="numpy", trials=1)
        np.testing.assert_array_equal(log_none.global_loss,
                                      log_off.global_loss)


class TestSpecValidation:
    @pytest.mark.parametrize("kw", [
        {"dropout_prob": -0.1}, {"dropout_prob": 1.5},
        {"erasure_prob": 2.0}, {"straggler_prob": -1.0},
        {"deep_fade_thresh": -1e-3}, {"straggler_mult": 0.5},
        {"deadline_s": 0.0}, {"deadline_s": -1.0},
        {"on_missing": "drop"},
    ])
    def test_bad_values_raise(self, kw):
        with pytest.raises(ValueError, match="fault\\."):
            FaultSpec(**kw)

    def test_survival_prob_composition(self):
        lam = np.array([1e-7, 1e-9])
        f = FaultSpec(dropout_prob=0.5, erasure_prob=0.5)
        np.testing.assert_allclose(survival_prob(f, lam), 0.25)
        # deep fades hit the weak device harder
        f2 = FaultSpec(deep_fade_thresh=1e-5)
        q = survival_prob(f2, lam)
        assert q[0] > q[1]
        np.testing.assert_allclose(q, np.exp(-1e-10 / lam))
        # deadline folds stragglers into the survival propensity
        f3 = FaultSpec(straggler_prob=0.4, deadline_s=1.0)
        np.testing.assert_allclose(survival_prob(f3, lam), 0.6)
        assert np.all(survival_prob(
            FaultSpec(dropout_prob=1.0), lam) >= 1e-12)

    def test_effective_lambdas(self):
        lam = np.array([1e-7, 1e-9])
        assert effective_lambdas(lam, FaultSpec()) is not None
        np.testing.assert_array_equal(effective_lambdas(lam, FaultSpec()),
                                      lam)
        f = FaultSpec(dropout_prob=0.5)
        np.testing.assert_allclose(effective_lambdas(lam, f), 0.5 * lam)
        # a fade threshold reduces delivered energy, never below the floor
        f2 = FaultSpec(deep_fade_thresh=1e-3)
        eff = effective_lambdas(lam, f2)
        assert np.all(eff > 0.0) and np.all(eff <= lam + 1e-6)

    def test_effective_participation_policies(self):
        p = np.array([0.5, 0.3, 0.2])
        q = np.array([1.0, 0.5, 0.1])
        np.testing.assert_array_equal(
            effective_participation(p, q, "zero"), p * q)
        np.testing.assert_array_equal(
            effective_participation(p, q, "reweight"), p)
        np.testing.assert_array_equal(
            effective_participation(p, q, "stale"), p)
        # zero-filling under heterogeneous survival adds structured bias
        assert (bias_sum(effective_participation(p, q, "zero"))
                != bias_sum(p))
        with pytest.raises(ValueError, match="on_missing"):
            effective_participation(p, q, "nope")


class TestSweepAxis:
    def test_fault_axes_sweepable_and_change_hashes(self):
        from repro.api.plan import plan
        from repro.api.spec import ScenarioSpec, SweepSpec

        base = ScenarioSpec(name="fault_axis")
        sweep = SweepSpec(name="fault_axis", base=base,
                          axes={"fault.dropout_prob": (0.0, 0.2),
                                "fault.on_missing": ("zero", "reweight")})
        pts = sweep.points()
        assert len(pts) == 4
        assert {sc.fault.dropout_prob for _, sc in pts} == {0.0, 0.2}
        assert len({sc.spec_hash() for _, sc in pts}) == 4
        cells = plan(sweep).cells
        assert len({c.cell_hash for c in cells}) == 4

    def test_from_dict_back_compat_without_fault_key(self):
        from repro.api.spec import ScenarioSpec

        d = ScenarioSpec(name="compat").to_dict()
        assert "fault" in d
        d.pop("fault")
        sc = ScenarioSpec.from_dict(d)
        assert sc.fault == FaultSpec() and not sc.fault.enabled

    def test_fault_round_trips_through_dict(self):
        from repro.api.spec import ScenarioSpec

        f = FaultSpec(dropout_prob=0.2, deadline_s=0.5, on_missing="stale")
        sc = ScenarioSpec(name="rt", fault=f)
        assert ScenarioSpec.from_dict(sc.to_dict()).fault == f

    def test_registered_sweep_fault_scenario_plans(self):
        from repro.api.plan import plan
        from repro.api.scenarios import sweep_fault

        sweep = sweep_fault(quick=True)
        assert sweep.base.fault.enabled
        pl = plan(sweep)
        assert len(pl.cells) == 4
