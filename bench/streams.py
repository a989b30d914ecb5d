"""The engine's documented random streams and link budget, written out for
the plain references (which import nothing of the program).

* Deployment (paper Sec. V): N devices uniform on a disk of radius
  rho_max, ``default_rng(seed)``: radii first; path loss
  ``PL0 + 10 Omega log10(max(s, s0)/s0)`` dB; Lambda = 10^(-PL/10).
* Fading, replayed: row t of trial r is drawn from
  ``default_rng(SeedSequence((1000 seed + r, t)))``, real then imaginary
  normals times sqrt(Lambda/2); the scan consumes it as complex64.
* PS noise, replayed: ``default_rng((seed, r, 17)).standard_normal((T, d))``.
* Dither, counter-based: the (N, d) uniforms of round t are
  ``uniform(fold_in(fold_in(fold_in(PRNGKey(seed mod 2^32), r), 17), t))``.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

DITHER_TAG = 17


def wireless_constants(w: dict):
    """(Lambda (N,), E_s [J/symbol], N0 [W/Hz]) of a wireless block."""
    rng = np.random.default_rng(w["seed"])
    s = w["rho_max_m"] * np.sqrt(rng.uniform(size=w["n_devices"]))
    pl = w["pl0_db"] + 10.0 * w["pl_exponent"] * np.log10(
        np.maximum(s, w["s0_m"]) / w["s0_m"])
    lam = 10.0 ** (-pl / 10.0)
    e_s = 10.0 ** (w["tx_power_dbm"] / 10.0) * 1e-3 / w["bandwidth_hz"]
    n0 = 10.0 ** (w["noise_psd_dbm_hz"] / 10.0) * 1e-3
    return lam, e_s, n0


def fading(lam: np.ndarray, seed: int, trial: int,
           rounds: int) -> np.ndarray:
    """(T, N) complex64 Rayleigh draws h ~ CN(0, Lambda) of one trial."""
    scale = np.sqrt(lam / 2.0)
    rows = []
    for t in range(rounds):
        rng = np.random.default_rng(
            np.random.SeedSequence(entropy=(int(seed) * 1000 + trial, t)))
        re = rng.normal(size=lam.shape[0]) * scale
        im = rng.normal(size=lam.shape[0]) * scale
        rows.append(re + 1j * im)
    return np.asarray(np.stack(rows), np.complex64)


def noise(seed: int, trial: int, rounds: int, d: int) -> np.ndarray:
    """(T, d) float32 standard normals of the PS's AWGN, one trial."""
    return np.asarray(np.random.default_rng((int(seed), trial, 17))
                      .standard_normal((rounds, d)), np.float32)


def dither_key(seed: int, trial: int):
    key = jax.random.PRNGKey(int(seed) & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, trial), DITHER_TAG)


def dither(key, t: int, n: int, d: int):
    """(n, d) float32 uniforms of round t."""
    return jax.random.uniform(jax.random.fold_in(key, t), (n, d),
                              dtype=jnp.float32)
