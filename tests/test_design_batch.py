"""Batched JAX design solver (core.sca_jax) vs the SciPy SCA oracle.

Parity contract: on every point of an (omega_var, omega_bias) grid the
batched solver's best-found true objective must be within rtol 1e-3 of —
or better than — the per-point SciPy SCA solution, for both the OTA (15)
and digital (17) problems.  benchmarks/design_bench.py enforces the same
gate at fig2 scale; these tests keep it in tier-1 at N=10.
"""
import numpy as np
import pytest

from repro.core.bounds import ObjectiveWeights
from repro.core.channel import WirelessConfig, make_deployment
from repro.core import digital_design, ota_design
from repro.core.sca_jax import ORACLE_RTOL as PARITY_RTOL

# The SciPy SCA oracle must run clean: re-anchored starts are clipped into
# the SLSQP box (core.sca.solve_surrogate) and the solver's internal
# mid-step clipping is scoped out at the source, so the once-ubiquitous
# "Values in x were outside bounds" RuntimeWarning escaping these solves is
# a regression. Promote exactly that message to an error here, on top of
# the repo-wide RuntimeWarning-as-error policy in pyproject.toml.
pytestmark = pytest.mark.filterwarnings(
    "error:Values in x were outside bounds:RuntimeWarning")


@pytest.fixture(scope="module")
def deployment():
    return make_deployment(WirelessConfig(n_devices=10, seed=1))


def _weight_grid(n, scales=(0.3, 3.0)):
    base = ObjectiveWeights.strongly_convex(eta=0.5, mu=0.01, kappa_sc=3.0,
                                            n=n)
    return [ObjectiveWeights(omega_var=base.omega_var * a,
                             omega_bias=base.omega_bias * b)
            for a in scales for b in scales]


def _ota_specs(dep, weights):
    cfg = dep.cfg
    return [ota_design.OTADesignSpec(
        lambdas=dep.lambdas, dim=7850, g_max=20.0,
        e_s=cfg.energy_per_symbol, n0=cfg.noise_power, weights=w)
        for w in weights]


def _dig_specs(dep, weights):
    cfg = dep.cfg
    return [digital_design.DigitalDesignSpec(
        lambdas=dep.lambdas, dim=7850, g_max=20.0,
        e_s=cfg.energy_per_symbol, n0=cfg.noise_power,
        bandwidth_hz=cfg.bandwidth_hz, t_max_s=0.2, weights=w)
        for w in weights]


class TestOTABatch:
    def test_parity_with_sca_oracle_on_grid(self, deployment):
        specs = _ota_specs(deployment, _weight_grid(deployment.n_devices))
        params, objs = ota_design.design_ota_batch(specs)
        for spec, p, f in zip(specs, params, objs):
            _, res = ota_design.design_ota_sca(spec, n_iters=6)
            assert f <= res.objective * (1.0 + PARITY_RTOL), (
                f, res.objective)
            # returned objective is the true objective at the returned design
            f_check = ota_design.true_objective_from_gamma(spec, p.gammas)
            np.testing.assert_allclose(f, f_check, rtol=1e-9)

    def test_batch_params_valid(self, deployment):
        specs = _ota_specs(deployment, _weight_grid(deployment.n_devices))
        params, _ = ota_design.design_ota_batch(specs)
        for spec, p in zip(specs, params):
            pl = p.participation_levels(deployment.lambdas)
            assert np.all(pl >= 0) and np.all(pl <= 1)
            np.testing.assert_allclose(pl.sum(), 1.0, rtol=1e-9)
            assert np.all(p.gammas <= spec.gamma_max() * (1 + 1e-12))

    def test_batch_matches_per_point_solve(self, deployment):
        """vmap must not mix grid points: batch == batch-of-one per spec.

        The specs differ in every traced field (weights, E_s, N0, dim) to
        exercise the fully-batched spec construction.
        """
        cfg = deployment.cfg
        w = _weight_grid(deployment.n_devices)[:3]
        specs = [ota_design.OTADesignSpec(
            lambdas=deployment.lambdas, dim=d, g_max=g,
            e_s=cfg.energy_per_symbol * se, n0=cfg.noise_power * sn,
            weights=wi)
            for wi, d, g, se, sn in zip(w, (7850, 3000, 500),
                                        (20.0, 10.0, 49.0),
                                        (1.0, 2.0, 0.5), (1.0, 0.5, 2.0))]
        _, objs = ota_design.design_ota_batch(specs)
        for spec, f in zip(specs, objs):
            _, f_single = ota_design.design_ota_batch([spec])
            np.testing.assert_allclose(f, f_single[0], rtol=1e-12)

    def test_stack_rejects_mismatched_device_count(self, deployment):
        specs = _ota_specs(deployment, _weight_grid(deployment.n_devices))[:1]
        cfg = deployment.cfg
        other = ota_design.OTADesignSpec(
            lambdas=deployment.lambdas[:5], dim=7850, g_max=20.0,
            e_s=cfg.energy_per_symbol, n0=cfg.noise_power,
            weights=specs[0].weights)
        with pytest.raises(ValueError, match="device count"):
            ota_design.stack_ota_specs(specs + [other])


class TestDigitalBatch:
    def test_parity_with_sca_oracle_on_grid(self, deployment):
        specs = _dig_specs(deployment, _weight_grid(deployment.n_devices))
        _, objs = digital_design.design_digital_batch(specs)
        for spec, f in zip(specs, objs):
            _, res = digital_design.design_digital_sca(spec, n_iters=4)
            assert f <= res.objective * (1.0 + PARITY_RTOL), (
                f, res.objective)

    def test_batch_params_valid(self, deployment):
        specs = _dig_specs(deployment, _weight_grid(deployment.n_devices))
        params, _ = digital_design.design_digital_batch(specs)
        for spec, p in zip(specs, params):
            pl = p.participation_levels(deployment.lambdas)
            np.testing.assert_allclose(pl.sum(), 1.0, rtol=1e-6)
            assert np.all(p.r_bits >= 1)
            assert np.all(p.r_bits <= spec.r_max)
            lat = p.expected_latency(deployment.lambdas)
            assert lat <= spec.t_max_s * 1.02, lat


class TestAnchors:
    def test_anchor_zero_bias_matches_scalar_bisection(self, deployment):
        """Vectorized bisection is bit-true to the per-device loop."""
        spec = _ota_specs(deployment,
                          _weight_grid(deployment.n_devices))[0]
        c = spec.c_m()
        target = float(np.min(spec.alpha_max())) * (1.0 - 1e-9)
        gmax = spec.gamma_max()
        expect = np.empty(spec.n)
        for m in range(spec.n):
            lo, hi = 0.0, gmax[m]
            for _ in range(200):
                mid = 0.5 * (lo + hi)
                if mid * np.exp(-c[m] * mid ** 2) < target:
                    lo = mid
                else:
                    hi = mid
            expect[m] = 0.5 * (lo + hi)
        np.testing.assert_array_equal(ota_design.anchor_zero_bias(spec),
                                      expect)

    def test_anchor_zero_bias_gives_uniform_p(self, deployment):
        spec = _ota_specs(deployment,
                          _weight_grid(deployment.n_devices))[0]
        gam = ota_design.anchor_zero_bias(spec)
        p = ota_design.params_from_gamma(
            spec, gam).participation_levels(deployment.lambdas)
        np.testing.assert_allclose(p, 1.0 / spec.n, rtol=1e-6)
