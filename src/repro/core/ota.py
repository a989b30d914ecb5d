"""Biased over-the-air (OTA) FL aggregation — Sec. II-A of the paper.

Uplink model (eq. (3)-(6)):
    y_t    = sum_m h_{m,t} x_{m,t} + z_t,         z_t ~ CN(0, N0 I)
    x_{m,t}= (1/h_{m,t}) * chi^A_{m,t} * gamma_m * g_{m,t}     (truncated inversion)
    chi^A  = 1{ |h_{m,t}| >= G_max * gamma_m / sqrt(d E_s) }   (eq. (5))
    ghat_t = y_t / alpha                                        (eq. (6))

Statistics:
    alpha_m(gamma_m) = gamma_m * exp(-gamma_m^2 G^2 / (d Lambda_m E_s))
    p_m = alpha_m / alpha,  alpha = sum_m alpha_m  (convex-combination bias)
    Lemma 1:  var(ghat|w) <= zeta_A
            = sum p_m^2 G^2 (gamma_m/alpha_m - 1)   [transmission]
            + sum p_m^2 sigma_m^2                   [mini-batch]
            + d N0 / alpha^2                        [AWGN]

The real-valued gradient of dimension d is carried over d/2 complex symbols
in practice; following the paper's notation we keep everything in the
d-dimensional real domain with noise variance d*N0/alpha^2 after
post-scaling (the per-component noise is N0/alpha^2).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np

from .channel import Deployment, participation_probability


@dataclasses.dataclass(frozen=True)
class OTAParams:
    """Offline-designed OTA-FL parameters (time-invariant during training)."""

    gammas: np.ndarray          # (N,) device pre-scalers gamma_m >= 0
    alpha: float                # PS post-scaler
    g_max: float                # gradient norm bound G_max (Assumption 1)
    dim: int                    # model dimension d
    energy_per_symbol: float    # E_s
    noise_psd: float            # N0

    def thresholds(self) -> np.ndarray:
        """Participation thresholds tau_m = G_max*gamma_m/sqrt(d E_s) (eq. (5))."""
        return self.g_max * self.gammas / np.sqrt(self.dim * self.energy_per_symbol)

    def alpha_m(self, lambdas: np.ndarray) -> np.ndarray:
        """alpha_m = gamma_m * exp(-gamma_m^2 G^2/(d Lambda_m E_s))."""
        ex = -(self.gammas ** 2) * self.g_max ** 2 / (
            self.dim * np.asarray(lambdas) * self.energy_per_symbol)
        return self.gammas * np.exp(ex)

    def participation_levels(self, lambdas: np.ndarray) -> np.ndarray:
        """p_m = alpha_m / alpha."""
        return self.alpha_m(lambdas) / self.alpha


def alpha_m_max(lambdas: np.ndarray, dim: int, e_s: float, g_max: float) -> np.ndarray:
    """max_gamma alpha_m(gamma) = sqrt(d Lambda E_s / (2 e G^2)) (Sec. IV-A)."""
    return np.sqrt(np.asarray(lambdas) * dim * e_s / (2.0 * np.e * g_max ** 2))


def gamma_m_max(lambdas: np.ndarray, dim: int, e_s: float, g_max: float) -> np.ndarray:
    """argmax_gamma alpha_m(gamma) = sqrt(d Lambda E_s / (2 G^2)) (Sec. IV-A)."""
    return np.sqrt(np.asarray(lambdas) * dim * e_s / (2.0 * g_max ** 2))


def lemma1_variance(params: OTAParams, lambdas: np.ndarray,
                    sigma_sq: Optional[np.ndarray] = None) -> dict:
    """Lemma 1 variance bound, decomposed into its three terms."""
    a_m = params.alpha_m(lambdas)
    p = a_m / params.alpha
    with np.errstate(divide="ignore", invalid="ignore"):
        ratio = np.where(a_m > 0, params.gammas / a_m, 1.0)
    transmission = float(np.sum(p ** 2 * params.g_max ** 2 * (ratio - 1.0)))
    if sigma_sq is None:
        minibatch = 0.0
    else:
        minibatch = float(np.sum(p ** 2 * np.asarray(sigma_sq)))
    noise = float(params.dim * params.noise_psd / params.alpha ** 2)
    return {
        "transmission": transmission,
        "minibatch": minibatch,
        "noise": noise,
        "total": transmission + minibatch + noise,
    }


def ota_round(params: OTAParams, grads: Sequence[np.ndarray], h: np.ndarray,
              rng: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
    """One OTA-FL uplink round (simulation path).

    Args:
      params: offline-designed OTA parameters.
      grads:  list of N local stochastic gradients g_{m,t} (dim d each).
      h:      complex fading realizations h_{m,t}, shape (N,).
      rng:    numpy RNG for the PS AWGN.

    Returns:
      (ghat, chi): the PS global-gradient estimate (eq. (6)) and the
      participation indicators chi^A_{m,t}.
    """
    d = params.dim
    taus = params.thresholds()
    chi = (np.abs(h) >= taus).astype(np.float64)
    acc = np.zeros(d, dtype=np.float64)
    for m, g in enumerate(grads):
        if chi[m]:
            # h_m x_m = chi * gamma_m * g_m exactly (perfect inversion above
            # the threshold); the energy constraint ||x||^2/d <= E_s holds by
            # construction of the threshold.
            acc += params.gammas[m] * np.asarray(g, dtype=np.float64)
    # Effective real-domain noise: each of the d real entries sees N(0, N0/2)
    # per complex dimension pair; following the paper's bound we use total
    # noise energy d*N0 i.e. per-entry variance N0.
    z = rng.normal(scale=np.sqrt(params.noise_psd), size=d)
    ghat = (acc + z) / params.alpha
    return ghat, chi


def ota_round_jax(params: OTAParams, grads, h, z01, *, use_kernel: bool = True):
    """One OTA-FL uplink round, pure-JAX (jit/vmap/scan-able).

    Numerically mirrors :func:`ota_round` — same thresholds, same truncated
    inversion, same post-scale — with the PS epilogue (post-scale + AWGN
    injection, eq. (6)) dispatched through the fused Pallas kernel
    ``kernels/ota_combine.py`` (interpret mode on CPU).

    Args:
      params: offline-designed OTA parameters (static under jit).
      grads:  (N, d) stacked local gradients.
      h:      (N,) complex fading realizations.
      z01:    (d,) standard-normal AWGN draws (scaled by sqrt(N0) here, so
              callers can replay the NumPy trainer's noise stream exactly).

    Returns:
      (ghat, chi): PS estimate (d,) and participation indicators (N,).
    """
    import jax.numpy as jnp

    from ..kernels import ops

    taus = jnp.asarray(params.thresholds())
    chi = (jnp.abs(h) >= taus).astype(grads.dtype)
    weights = chi * jnp.asarray(params.gammas, grads.dtype)
    acc = weights @ grads
    z = np.sqrt(params.noise_psd) * z01
    ghat = ops.ota_combine_with_noise(acc, params.alpha, z,
                                      use_kernel=use_kernel)
    return ghat, chi


def opc_ota_fl_round_jax(grads, h, z01, *, dim: int, g_max: float,
                         e_s: float, n0: float, use_kernel: bool = True):
    """[20] genie-aided OPC OTA-FL round, pure-JAX (jit/vmap/scan-able).

    Mirrors ``baselines.OPCOTAFL.round``: evaluate the include-k-strongest
    bias/noise proxy on every k = 1..N threshold candidate at once, pick the
    first minimizer (matching the oracle's strict-< scan), and aggregate the
    selected set with the common inversion pre-scaler. The PS epilogue goes
    through the fused Pallas combine kernel.
    """
    import jax.numpy as jnp

    from ..kernels import ops

    habs = jnp.abs(h)
    n = habs.shape[0]
    order = jnp.argsort(habs)[::-1]
    habs_desc = habs[order]
    ks = jnp.arange(1, n + 1, dtype=jnp.float32)
    gammas = np.sqrt(dim * e_s) * habs_desc / g_max
    scores = (g_max ** 2 * (1.0 - ks / n) ** 2
              + dim * n0 / (ks * gammas) ** 2)
    kidx = jnp.argmin(scores)             # first minimum, as the oracle
    k = (kidx + 1).astype(jnp.float32)
    gamma = gammas[kidx]
    chi = jnp.zeros(n, grads.dtype).at[order].set(
        (jnp.arange(n) <= kidx).astype(grads.dtype))
    acc = gamma * (chi @ grads)
    ghat = ops.ota_combine_with_noise(acc, k * gamma,
                                      np.sqrt(n0) * z01,
                                      use_kernel=use_kernel)
    return ghat, chi


def bbfl_round_jax(grads, h, z01, t, *, dim: int, g_max: float, e_s: float,
                   n0: float, gamma_odd: float, mask_odd,
                   gamma_even: float, mask_even,
                   use_kernel: bool = True):
    """[16] broadband analog aggregation round, pure-JAX.

    Covers both BB-FL variants through the round-parity input ``t``:
    odd rounds use (``gamma_odd``, ``mask_odd``), even rounds
    (``gamma_even``, ``mask_even``). BB-FL *Interior* passes the same
    interior policy for both parities; BB-FL *Alternative* passes the
    all-device policy for even rounds, matching the oracle's ``t % 2``
    schedule. Truncated inversion inside the scheduled mask, PS divides by
    ``max(|S_t|, 1) * gamma``.
    """
    import jax.numpy as jnp

    from ..kernels import ops

    odd = (t % 2) == 1
    gamma = jnp.where(odd, gamma_odd, gamma_even)
    mask = jnp.where(odd, jnp.asarray(mask_odd), jnp.asarray(mask_even))
    tau = g_max * gamma / np.sqrt(dim * e_s)
    chi = ((jnp.abs(h) >= tau) & (mask > 0)).astype(grads.dtype)
    k = jnp.sum(chi)
    acc = gamma * (chi @ grads)
    denom = jnp.maximum(k, 1.0) * gamma
    ghat = ops.ota_combine_with_noise(acc, denom, np.sqrt(n0) * z01,
                                      use_kernel=use_kernel)
    return ghat, chi


def expected_participation(params: OTAParams, lambdas: np.ndarray) -> np.ndarray:
    """E[chi^A_m] = exp(-tau_m^2/Lambda_m)."""
    return participation_probability(params.thresholds(), lambdas)


def uniform_gamma_min_variance(lambdas: np.ndarray, dim: int, e_s: float,
                               g_max: float, n0: float,
                               n_grid: int = 4096) -> float:
    """Common pre-scaler minimizing the Lemma-1 variance bound.

    Used by the LCPC OTA-Comp baseline: all devices share one gamma; returns
    the scalar grid-minimizer of the Lemma-1 bound (statistical CSI only).
    """
    lambdas = np.asarray(lambdas)
    g_hi = float(np.min(gamma_m_max(lambdas, dim, e_s, g_max)))
    grid = np.linspace(1e-4 * g_hi, g_hi, n_grid)
    best, best_v = grid[0], np.inf
    for gmm in grid:
        gam = np.full(lambdas.shape, gmm)
        ex = -(gam ** 2) * g_max ** 2 / (dim * lambdas * e_s)
        a_m = gam * np.exp(ex)
        alpha = float(np.sum(a_m))
        p = a_m / alpha
        v = float(np.sum(p ** 2 * g_max ** 2 * (gam / a_m - 1.0))
                  + dim * n0 / alpha ** 2)
        if v < best_v:
            best, best_v = gmm, v
    return float(best)
