"""rng="fast" execution-mode contracts (counter-based in-scan streams).

Fast mode regenerates every random stream — fading, PS AWGN, selection,
dither, batch indices — as pure threefry functions of
``(seed, trial, round, stream)`` inside the engine's scan. The draws come
from the *same laws* as the replay oracle's but form a different stream,
so the guarantees tested here are:

  * statistical equivalence: mean trajectories agree within Monte-Carlo
    error (the CI smoke gate for the mode),
  * distinctness: per-trial trajectories differ from replay (fast is not
    secretly replay),
  * degenerate exactness: a scheme that consumes *only* counter-based
    randomness (IdealFedAvg + mini-batch) is bit-identical across modes,
  * zero host-side precompute: fast mode never touches the oracle's
    sequential ``trial_rng`` or ``sample_fading_batch`` (monkeypatched to
    explode),
  * dispatch: fast is engine-only, and ``run.rng`` is a sweepable axis
    that changes every cell hash.
"""
import numpy as np
import pytest

from repro.core import baselines as B
from repro.core import rngstream
from repro.core.channel import WirelessConfig, make_deployment
from repro.data.loader import FLDataset
from repro.data.partition import partition_by_class
from repro.data.synthetic import SyntheticSpec, make_classification_dataset
from repro.fl import engine as engine_mod
from repro.fl.trainer import FLTrainer

N_DEVICES = 10


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


def _run(setup, agg, *, rng, trials, rounds=30, eval_every=10, seed=5,
         batch_size=None):
    task, ds, dep, eta = setup
    tr = FLTrainer(task, ds, dep, eta=eta, batch_size=batch_size)
    return tr.run(agg, rounds=rounds, trials=trials, eval_every=eval_every,
                  seed=seed, backend="jax", rng=rng)


def _assert_statistically_equivalent(log_r, log_f):
    """Mean trajectories within 4x the combined Monte-Carlo stderr."""
    lr, lf = log_r.global_loss, log_f.global_loss
    mr, mf = lr.mean(axis=0), lf.mean(axis=0)
    stderr = np.sqrt(lr.var(axis=0, ddof=1) / lr.shape[0]
                     + lf.var(axis=0, ddof=1) / lf.shape[0])
    gap = np.abs(mr - mf)
    assert np.all(gap <= 4.0 * stderr + 1e-7), (gap, stderr)


class TestStatisticalEquivalence:
    def test_ota_awgn_and_fading(self, setup):
        """VanillaOTA consumes fading + PS AWGN — the two streams fast
        mode re-keys — so its trajectory is the core equivalence gate."""
        task, _, dep, _ = setup
        args = (task.dim, task.g_max, dep.cfg.energy_per_symbol,
                dep.cfg.noise_power)
        log_r = _run(setup, B.VanillaOTA(*args), rng="replay", trials=12)
        log_f = _run(setup, B.VanillaOTA(*args), rng="fast", trials=12)
        _assert_statistically_equivalent(log_r, log_f)

    def test_digital_selection_and_dither(self, setup):
        """UQOS exercises the fast selection sampler (sel_stream_jax) plus
        the (mode-shared) counter-based dither stream."""
        task, _, dep, _ = setup
        agg_kw = (dep, task.dim, task.g_max, dep.cfg.energy_per_symbol,
                  dep.cfg.noise_power, dep.cfg.bandwidth_hz)
        log_r = _run(setup, B.UQOS(*agg_kw), rng="replay", trials=8,
                     rounds=20)
        log_f = _run(setup, B.UQOS(*agg_kw), rng="fast", trials=8,
                     rounds=20)
        _assert_statistically_equivalent(log_r, log_f)

    def test_fast_stream_actually_differs(self, setup):
        """Fast is a *different* stream, not replay under a new name."""
        task, _, dep, _ = setup
        args = (task.dim, task.g_max, dep.cfg.energy_per_symbol,
                dep.cfg.noise_power)
        log_r = _run(setup, B.VanillaOTA(*args), rng="replay", trials=2)
        log_f = _run(setup, B.VanillaOTA(*args), rng="fast", trials=2)
        assert not np.allclose(log_r.global_loss[:, -1],
                               log_f.global_loss[:, -1], rtol=1e-10)

    def test_counter_only_scheme_is_bit_identical(self, setup):
        """IdealFedAvg + mini-batch consumes *only* the batch stream,
        which is counter-based in both modes — trajectories must match
        exactly, pinning down that fast mode re-keys nothing it needn't."""
        log_r = _run(setup, B.IdealFedAvg(), rng="replay", trials=2,
                     rounds=20, batch_size=32)
        log_f = _run(setup, B.IdealFedAvg(), rng="fast", trials=2,
                     rounds=20, batch_size=32)
        np.testing.assert_array_equal(log_r.global_loss, log_f.global_loss)
        np.testing.assert_array_equal(log_r.accuracy, log_f.accuracy)


class TestZeroPrecompute:
    def _explode(self, *a, **k):
        raise AssertionError(
            "host-side per-trial RNG precompute reached in fast mode")

    def test_fast_never_touches_host_streams(self, setup, monkeypatch):
        """Fast mode's whole host-side RNG footprint is three (2,)-uint32
        base keys per trial: the oracle fading sampler and the sequential
        trial generator must never be called."""
        task, ds, dep, eta = setup
        monkeypatch.setattr(engine_mod, "sample_fading_batch", self._explode)
        monkeypatch.setattr(rngstream, "trial_rng", self._explode)
        args = (task.dim, task.g_max, dep.cfg.energy_per_symbol,
                dep.cfg.noise_power)
        log = FLTrainer(task, ds, dep, eta=eta).run(
            B.VanillaOTA(*args), rounds=8, trials=2, eval_every=4, seed=3,
            backend="jax", rng="fast")
        assert np.all(np.isfinite(log.global_loss))
        # sanity: the same patched world breaks replay, so the patch bites
        with pytest.raises(AssertionError, match="precompute"):
            FLTrainer(task, ds, dep, eta=eta).run(
                B.VanillaOTA(*args), rounds=8, trials=2, eval_every=4,
                seed=3, backend="jax", rng="replay")


class TestDispatch:
    def test_rng_validation(self, setup):
        task, ds, dep, eta = setup
        tr = FLTrainer(task, ds, dep, eta=eta)
        with pytest.raises(ValueError, match="rng must be"):
            tr.run(B.IdealFedAvg(), rounds=4, trials=1, eval_every=2,
                   rng="nope")

    def test_fast_rejected_on_numpy_backend(self, setup):
        task, ds, dep, eta = setup
        tr = FLTrainer(task, ds, dep, eta=eta)
        with pytest.raises(ValueError, match="replay oracle"):
            tr.run(B.IdealFedAvg(), rounds=4, trials=1, eval_every=2,
                   backend="numpy", rng="fast")

    def test_fast_rejected_for_unported_scheme(self, setup):
        class Unported(B.Aggregator):
            name = "unported"

            def round(self, grads, h, t, rng, dither=None):
                g = np.mean(np.stack([np.asarray(g) for g in grads]), 0)
                return B.RoundResult(g, 0.0, np.ones(len(grads)), {})

        task, ds, dep, eta = setup
        tr = FLTrainer(task, ds, dep, eta=eta)
        with pytest.raises(ValueError, match="NumPy path"):
            tr.run(Unported(), rounds=4, trials=1, eval_every=2, rng="fast")


class TestSweepAxis:
    def test_run_rng_is_sweepable_and_changes_hashes(self):
        from repro.api.plan import plan
        from repro.api.spec import ScenarioSpec, SweepSpec

        base = ScenarioSpec(name="rng_axis")
        sweep = SweepSpec(name="rng_axis", base=base,
                          axes={"run.rng": ("replay", "fast")})
        pts = sweep.points()
        assert [sc.run.rng for _, sc in pts] == ["replay", "fast"]
        hashes = {sc.spec_hash() for _, sc in pts}
        assert len(hashes) == 2
        cells = plan(sweep).cells
        assert len(cells) == 2
        assert len({c.cell_hash for c in cells}) == 2


class TestPayloadDtype:
    """run.payload_dtype="bf16": half-width uplink gradient payloads with
    f32 accumulation — a lossy knob, so the gate is the fast-RNG suite's
    statistical-equivalence test, not bit parity."""

    def _run_pd(self, setup, agg, payload_dtype, *, trials, rounds=30,
                seed=5):
        task, ds, dep, eta = setup
        tr = FLTrainer(task, ds, dep, eta=eta, payload_dtype=payload_dtype)
        return tr.run(agg, rounds=rounds, trials=trials, eval_every=10,
                      seed=seed, backend="jax")

    def test_bf16_statistically_equivalent_to_f32(self, setup):
        """bf16 payload rounding is a small perturbation next to the
        channel noise: mean trajectories agree within Monte-Carlo error."""
        task, _, dep, _ = setup
        args = (task.dim, task.g_max, dep.cfg.energy_per_symbol,
                dep.cfg.noise_power)
        log32 = self._run_pd(setup, B.VanillaOTA(*args), "f32", trials=12)
        log16 = self._run_pd(setup, B.VanillaOTA(*args), "bf16", trials=12)
        _assert_statistically_equivalent(log32, log16)

    def test_bf16_actually_differs(self, setup):
        """The cast must bite — bf16 is not silently f32."""
        task, _, dep, _ = setup
        args = (task.dim, task.g_max, dep.cfg.energy_per_symbol,
                dep.cfg.noise_power)
        log32 = self._run_pd(setup, B.VanillaOTA(*args), "f32", trials=2)
        log16 = self._run_pd(setup, B.VanillaOTA(*args), "bf16", trials=2)
        assert not np.allclose(log32.global_loss, log16.global_loss,
                               rtol=1e-10)

    def test_bf16_payload_is_the_bf16_rounding_of_f32(self, setup):
        """The payload the combiner sees is each f32 gradient rounded to
        the nearest bf16 (ties to even), as NumPy's bf16 rounds it: with
        w0 = 0, eta = 1 and a combiner that passes device 0's payload on,
        the state after one round is minus that payload."""
        import jax.numpy as jnp
        from repro.fl.engine import FLEngine, JaxAggregator

        task, ds, dep, _ = setup
        first = JaxAggregator(name="first", is_ota=True, needs_noise=False,
                              round_fn=lambda g, h, z, u, sel, t: (g[0], 0.))
        ws = {}
        for dtype in ("f32", "bf16"):
            eng = FLEngine(task, ds, dep, 1.0, payload_dtype=dtype)
            _, runner, args = eng.prepare(first, rounds=1, trials=1,
                                          eval_every=1, seed=5)
            ws[dtype] = np.asarray(runner(*args)[0])[0, 1]
        assert not np.any(np.asarray(task.init_params()))
        rounded = ws["f32"].astype(jnp.bfloat16).astype(np.float32)
        np.testing.assert_array_equal(ws["bf16"], rounded)
        assert not np.array_equal(ws["bf16"], ws["f32"])

    def test_bf16_rejected_on_numpy_backend(self, setup):
        task, ds, dep, eta = setup
        tr = FLTrainer(task, ds, dep, eta=eta, payload_dtype="bf16")
        with pytest.raises(ValueError, match="JAX engine"):
            tr.run(B.IdealFedAvg(), rounds=4, trials=1, eval_every=2,
                   backend="numpy")

    def test_bf16_rejected_for_unported_scheme(self, setup):
        class Unported(B.Aggregator):
            name = "unported"

            def round(self, grads, h, t, rng, dither=None):
                g = np.mean(np.stack([np.asarray(g) for g in grads]), 0)
                return B.RoundResult(g, 0.0, np.ones(len(grads)), {})

        task, ds, dep, eta = setup
        tr = FLTrainer(task, ds, dep, eta=eta, payload_dtype="bf16")
        with pytest.raises(ValueError, match="NumPy path"):
            tr.run(Unported(), rounds=4, trials=1, eval_every=2)

    def test_payload_dtype_validation(self, setup):
        task, ds, dep, eta = setup
        with pytest.raises(ValueError, match="payload_dtype"):
            FLTrainer(task, ds, dep, eta=eta, payload_dtype="f16")

    def test_run_payload_dtype_is_sweepable_and_changes_hashes(self):
        from repro.api.plan import plan
        from repro.api.spec import ScenarioSpec, SweepSpec

        base = ScenarioSpec(name="pd_axis")
        sweep = SweepSpec(name="pd_axis", base=base,
                          axes={"run.payload_dtype": ("f32", "bf16")})
        pts = sweep.points()
        assert [sc.run.payload_dtype for _, sc in pts] == ["f32", "bf16"]
        assert len({sc.spec_hash() for _, sc in pts}) == 2
        cells = plan(sweep).cells
        assert len(cells) == 2
        assert len({c.cell_hash for c in cells}) == 2
