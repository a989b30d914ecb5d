"""Biased digital (TDMA + quantized) FL aggregation — Sec. II-B of the paper.

Uplink model (eq. (9)-(12)):
    chi^D_{m,t} = 1{ |h_{m,t}| >= rho_m }              (eq. (9))
    device m transmits its dithered-quantized gradient (r_m bits/entry,
    payload L_m = 64 + d r_m) at fixed spectral efficiency
        R_m = log2(1 + E_s rho_m^2 / N0)   [bits/s/Hz]
    (outage-free by the threshold rule — unless the fault layer injects
    deep fades below ``core.faults.FaultSpec.deep_fade_thresh``; both the
    in-allocation rule and injected outages evaluate through the single
    :func:`outage_mask` primitive); uplink latency L_m/(B R_m).
    ghat_t = sum_m chi^D_{m,t} g^q_{m,t} / nu_m        (eq. (10))

Statistics:
    beta_m = E[chi^D] = exp(-rho_m^2/Lambda_m),  p_m = beta_m / nu_m
    Lemma 2: var(ghat|w) <= zeta_D
           = sum p^2 G^2 (1/beta - 1)                    [transmission]
           + sum p^2 sigma^2                             [mini-batch]
           + sum p^2 G^2 d / (beta (2^r - 1)^2)          [quantization]
    Expected per-round latency (12): sum_m beta_m L_m / (B R_m).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np

from .quantize import payload_bits, quantize_np, quantize_np_dither


def outage_mask(habs, thr, deep_fade_thresh: float = 0.0):
    """The one threshold rule: 1{ |h| >= max(thr, deep_fade_thresh) }.

    Every "no outage" comparison — the digital in-allocation rule eq. (9)
    and the fault layer's injected deep fades — routes through this
    primitive so the two masks compose in one place. ``thr`` and
    ``deep_fade_thresh`` are static (numpy/Python) values; ``habs`` may be
    a numpy array (oracle) or a traced jnp array (engine scan), and the
    comparison dispatches accordingly. With ``deep_fade_thresh=0`` the
    effective threshold is exactly ``thr`` (thresholds are nonnegative),
    preserving bit-identical pre-fault behavior.
    """
    return habs >= np.maximum(thr, deep_fade_thresh)


@dataclasses.dataclass(frozen=True)
class DigitalParams:
    """Offline-designed digital-FL parameters (time-invariant)."""

    rhos: np.ndarray            # (N,) participation thresholds rho_m
    nus: np.ndarray             # (N,) PS post-scalers nu_m
    r_bits: np.ndarray          # (N,) quantization bits r_m (ints >= 1)
    g_max: float
    dim: int
    energy_per_symbol: float
    noise_psd: float
    bandwidth_hz: float

    def betas(self, lambdas: np.ndarray) -> np.ndarray:
        """beta_m = exp(-rho_m^2 / Lambda_m)."""
        return np.exp(-(self.rhos ** 2) / np.asarray(lambdas))

    def participation_levels(self, lambdas: np.ndarray) -> np.ndarray:
        """p_m = beta_m / nu_m."""
        return self.betas(lambdas) / self.nus

    def rates(self) -> np.ndarray:
        """R_m = log2(1 + E_s rho_m^2/N0) [bits/s/Hz] (eq. (17c))."""
        snr = self.energy_per_symbol * self.rhos ** 2 / self.noise_psd
        return np.log2(1.0 + snr)

    def payloads(self) -> np.ndarray:
        return np.array([payload_bits(self.dim, int(r)) for r in self.r_bits],
                        dtype=np.float64)

    def expected_latency(self, lambdas: np.ndarray) -> float:
        """Expected per-round uplink latency (eq. (12)) [s]."""
        rates = np.maximum(self.rates(), 1e-12)
        return float(np.sum(self.betas(lambdas) * self.payloads()
                            / (self.bandwidth_hz * rates)))


def lemma2_variance(params: DigitalParams, lambdas: np.ndarray,
                    sigma_sq: Optional[np.ndarray] = None) -> dict:
    """Lemma 2 variance bound, decomposed into its three terms."""
    beta = params.betas(lambdas)
    p = beta / params.nus
    g2 = params.g_max ** 2
    transmission = float(np.sum(p ** 2 * g2 * (1.0 / beta - 1.0)))
    minibatch = 0.0 if sigma_sq is None else float(np.sum(p ** 2 * np.asarray(sigma_sq)))
    s = (2.0 ** params.r_bits.astype(np.float64) - 1.0) ** 2
    quant = float(np.sum(p ** 2 * g2 * params.dim / (beta * s)))
    return {
        "transmission": transmission,
        "minibatch": minibatch,
        "quantization": quant,
        "total": transmission + minibatch + quant,
    }


def digital_round(params: DigitalParams, grads: Sequence[np.ndarray],
                  h: np.ndarray, rng: np.random.Generator,
                  dither: Optional[np.ndarray] = None
                  ) -> tuple[np.ndarray, np.ndarray, float]:
    """One digital-FL uplink round (simulation path).

    ``dither``: optional (N, d) per-device dither uniforms (the trainer
    passes the counter-based ``core.rngstream`` block so the JAX engine can
    replay the stream per round); when None, dither is drawn sequentially
    from ``rng`` as in standalone use.

    Returns (ghat, chi, latency_s): PS estimate (eq. (10)), participation
    indicators, and the realized round latency (sum over participating
    devices of L_m/(B R_m), TDMA).
    """
    d = params.dim
    chi = outage_mask(np.abs(h), params.rhos).astype(np.float64)
    acc = np.zeros(d, dtype=np.float64)
    rates = np.maximum(params.rates(), 1e-12)
    payloads = params.payloads()
    latency = 0.0
    for m, g in enumerate(grads):
        if chi[m]:
            g64 = np.asarray(g, dtype=np.float64)
            if dither is None:
                gq = quantize_np(g64, int(params.r_bits[m]), rng)
            else:
                gq = quantize_np_dither(g64, int(params.r_bits[m]), dither[m])
            acc += gq / params.nus[m]
            latency += payloads[m] / (params.bandwidth_hz * rates[m])
    return acc, chi, float(latency)


def digital_round_jax(params: DigitalParams, grads, h, u,
                      *, use_kernel: bool = True):
    """One digital-FL uplink round, pure-JAX (jit/vmap/scan-able).

    Numerically mirrors :func:`digital_round` — same threshold rule, same
    PS reweighting, same TDMA latency — with each device's dithered
    quantize-dequantize dispatched through the fused Pallas kernel
    ``kernels/dithered_quant.py`` (interpret mode on CPU).

    Args:
      params: offline-designed digital parameters (static under jit).
      grads:  (N, d) stacked local gradients.
      h:      (N,) complex fading realizations.
      u:      (N, d) dither uniforms, one row per device. Passing the NumPy
              trainer's dither stream row-for-row reproduces its quantized
              payloads bit-for-bit (up to 1-ulp kernel rounding).

    Returns:
      (ghat, chi, latency_s): PS estimate (d,), participation indicators
      (N,), and the realized TDMA round latency [s].
    """
    import jax.numpy as jnp

    from ..kernels import ops

    chi = outage_mask(jnp.abs(h), params.rhos).astype(grads.dtype)
    rates = np.maximum(params.rates(), 1e-12)
    lat_m = jnp.asarray(params.payloads() / (params.bandwidth_hz * rates))
    levels = (2.0 ** params.r_bits.astype(np.float64) - 1.0)
    # static r_max bound lets the payload-scale fused pack path engage at
    # large d (quantize straight into uint32 codes, O(d) accumulate)
    acc = ops.quantized_weighted_sum(
        grads, jnp.asarray(levels), u, chi / jnp.asarray(params.nus),
        r_max=int(np.max(params.r_bits)), use_kernel=use_kernel)
    latency = jnp.sum(chi * lat_m)
    return acc, chi, latency


# ----------------------------------------- jittable selection primitives
#
# The digital baseline suite (Sec. V-A-2) is built from three reusable
# jit/vmap/scan-able pieces: instantaneous capacity rates, top-K device
# selection as a 0/1 mask, and FedTOE's greedy bit allocation. The NumPy
# oracle implementations live in ``core.baselines``; these mirror them
# op-for-op so trajectories replay to the engine's f32 round-off.

def capacity_rate_jnp(habs, e_s: float, n0: float):
    """Instantaneous spectral efficiency log2(1 + E_s|h|^2/N0) [b/s/Hz]."""
    import jax.numpy as jnp

    return jnp.log2(1.0 + e_s * habs ** 2 / n0)


def topk_mask(score, k: int):
    """0/1 mask of the k highest-scoring devices.

    Mirrors the oracle's ``np.argsort(score)[::-1][:k]`` (ties broken by
    sort order — measure-zero for the continuous channel scores used here).
    """
    import jax.numpy as jnp

    n = score.shape[0]
    order = jnp.argsort(score)[::-1]
    return jnp.zeros(n, score.dtype).at[order[:k]].set(1.0)


def greedy_bit_alloc_jax(sel, rates, *, dim: int, bandwidth_hz: float,
                         t_budget_s: float, r_max: int):
    """FedTOE's greedy RB/bit allocation as a jittable scan + while_loop.

    Mirrors ``baselines.FedTOE._alloc_bits``: walk the scheduled set in
    decreasing-rate order giving each device 1 bit while its minimum
    payload fits the round budget (``lax.scan``), then greedily grant +1
    bit to the device with the best variance-reduction-per-latency gain
    until the budget or ``r_max`` saturates (``lax.while_loop``).

    Args:
      sel:   (k,) int device indices scheduled this round (replayed draw).
      rates: (N,) static per-device spectral efficiencies R_m.

    Returns:
      (bits, in_alloc): (N,) float bit-widths (0 for devices outside the
      allocation) and the 0/1 allocation mask.
    """
    import jax
    import jax.numpy as jnp

    n = rates.shape[0]
    rates = jnp.asarray(rates, jnp.float32)
    safe_rates = jnp.maximum(rates, 1e-9)
    # stable descending-rate order over the scheduled set, mirroring
    # ``sorted(sel, key=lambda m: -rates[m])``
    order = jnp.argsort(-rates[sel])
    sel_sorted = sel[order]
    t_one = (64.0 + dim) / (bandwidth_hz * safe_rates[sel_sorted])

    def fill(used, t1):
        fits = used + t1 <= t_budget_s
        return used + jnp.where(fits, t1, 0.0), fits

    _, fits = jax.lax.scan(fill, jnp.zeros((), jnp.float32), t_one)
    in_alloc = jnp.zeros(n, jnp.float32).at[sel_sorted].add(
        fits.astype(jnp.float32))
    bits0 = in_alloc.copy()
    per_bit_s = dim / (bandwidth_hz * safe_rates)

    def latency(bits):
        return jnp.sum(in_alloc * (64.0 + dim * bits)
                       / (bandwidth_hz * safe_rates))

    def cond(state):
        _, done = state
        return jnp.logical_not(done)

    def body(state):
        # under vmap the loop runs until every lane is done, so ``done``
        # must freeze a lane's state (accept is forced False once done)
        bits, done = state
        eligible = (in_alloc > 0) & (bits < r_max)
        b_safe = jnp.where(in_alloc > 0, bits, 1.0)
        dv = (1.0 / (2.0 ** b_safe - 1.0) ** 2
              - 1.0 / (2.0 ** (b_safe + 1.0) - 1.0) ** 2)
        gain = jnp.where(eligible, dv / per_bit_s, 0.0)
        best = jnp.argmax(gain)
        bits_new = bits.at[best].add(1.0)
        accept = ((gain[best] > 0.0) & (latency(bits_new) <= t_budget_s)
                  & jnp.logical_not(done))
        return jnp.where(accept, bits_new, bits), jnp.logical_not(accept)

    bits, _ = jax.lax.while_loop(cond, body,
                                 (bits0, jnp.sum(in_alloc) == 0))
    return bits, in_alloc
