"""Buffered-asynchronous FL: stationary staleness as priced structured bias.

Everything else in the repo is round-synchronous: every device's round-``t``
gradient is computed at the round-``t`` model. This module supplies the
buffered-async execution mode (``run.mode="async"``) both simulation
backends share, built on the same counter-based-stream / strict-no-op
contracts as the fault and participation layers:

  * **Heterogeneous arrivals.** Device ``m`` completes a local update in a
    given round with static per-round probability ``r_m``
    (:func:`arrival_rates`: a log-spread around ``arrival_rate`` controlled
    by ``rate_heterogeneity`` — the straggler distribution). Per round, one
    (2, N) uniform block from the counter-based ARRIVAL stream
    (``core.rngstream``, a pure threefry function of
    ``(seed, trial, round)``) drives a delivery event (``u0 < r_m``) and a
    staleness draw for the delivered update.
  * **Stationary staleness.** A delivered update was computed ``S`` rounds
    ago with ``S`` geometric(``r_m``): slow devices deliver stale
    gradients. The PS buffers the last ``K = buffer_rounds`` rounds of
    per-device gradients (a scan-carried (K, N, d) window in the JAX
    engine); draws with ``S >= K`` fall outside the buffer window and are
    discarded. The staleness CDF thresholds (:func:`staleness_cdf`) and
    rates are precomputed host-side in float64 and rounded to float32
    (``rngstream.f32_table``) before either backend compares the f32
    uniforms against them, so the realized
    delivery/staleness pattern is *bit-identical* across the NumPy oracle,
    the JAX engine, and both rng modes — only exact comparisons against
    shared tables, never transcendentals, happen inside the round loop.
  * **Staleness-discounted delivery.** The payload entering every
    registered scheme's combiner is ``delta^S * v_m * (N / sum(c v)) *
    g_m(w_{t-S})``: the staleness discount ``delta = staleness_discount``,
    a per-device PS weight ``v_m`` (uniform 1, or the co-designed weights
    from ``core.sca_jax.solve_async_batch``), and a global normalization
    that keeps the expected delivered mass at N. Missing devices zero-fill
    (``on_missing="zero"``, the priced default) or replay their last
    delivered payload (``"stale"`` — the same single last-gradient code
    path, :func:`stale_replace`, that backs ``fault.on_missing="stale"``).

Because the staleness distribution is *stationary*, the induced shift is a
structured, time-invariant tilt of the effective participation levels:
``e_m = p_m * c_m * v_m * (N / sum(c v))`` with
``c_m = E[delta^S; delivered within the window]``
(:func:`delivery_weight`) — exactly the kind of bias the Theorem-1/2
bound prices through ``bounds.async_effective_participation`` /
``bounds.bias_sum``, composing with the fault (q) and sampling (pi)
factors that already tilt ``p``.

``run.mode="sync"`` (the default) disables the layer entirely:
:func:`resolve` returns None and both backends trace/execute their exact
pre-async programs (bit-identical trajectories, the ``FaultSpec`` /
``core.participation`` strict-no-op contract).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np
import jax.numpy as jnp

MODES = ("sync", "async")
ON_MISSING = ("zero", "stale")
WEIGHTINGS = ("uniform", "designed")

#: Floor on per-device arrival rates (a rate of 0 would make the staleness
#: geometry degenerate and the device silent forever).
RATE_MIN = 1e-3


@dataclasses.dataclass(frozen=True)
class AsyncSpec:
    """Buffered-async knobs (``async_.*`` sweep axes; inert under
    ``run.mode="sync"``).

    buffer_rounds       K — staleness buffer depth; delivered updates carry
                        staleness S in {0, ..., K-1}, older draws are
                        discarded (fell out of the buffer window).
    arrival_rate        mean per-round completion probability r of a device
                        (1.0 = every device delivers a fresh update every
                        round — the synchronous limit).
    rate_heterogeneity  log-spread of the per-device rates: device rates
                        span ``arrival_rate * (1+h)^{±1}`` across the
                        population (0 = homogeneous; the straggler axis).
    staleness_discount  delta — multiplicative weight ``delta^S`` on a
                        staleness-S payload (1.0 = undiscounted).
    on_missing          "zero" (priced bias, default) | "stale" (replay the
                        last delivered payload, :func:`stale_replace`).
    weighting           "uniform" — v = 1; "designed" — per-device PS
                        weights from ``sca_jax.solve_async_batch`` (must be
                        passed explicitly to the trainer/engine).
    """

    buffer_rounds: int = 4
    arrival_rate: float = 0.7
    rate_heterogeneity: float = 0.0
    staleness_discount: float = 1.0
    on_missing: str = "zero"
    weighting: str = "uniform"

    def __post_init__(self):
        if int(self.buffer_rounds) < 1:
            raise ValueError(
                f"buffer_rounds must be >= 1, got {self.buffer_rounds!r}")
        if not 0.0 < float(self.arrival_rate) <= 1.0:
            raise ValueError(
                f"arrival_rate must be in (0, 1], got {self.arrival_rate!r}")
        if float(self.rate_heterogeneity) < 0.0:
            raise ValueError(
                "rate_heterogeneity must be >= 0, got "
                f"{self.rate_heterogeneity!r}")
        if not 0.0 < float(self.staleness_discount) <= 1.0:
            raise ValueError(
                "staleness_discount must be in (0, 1], got "
                f"{self.staleness_discount!r}")
        if self.on_missing not in ON_MISSING:
            raise ValueError(
                f"async on_missing must be one of {ON_MISSING}, got "
                f"{self.on_missing!r}")
        if self.weighting not in WEIGHTINGS:
            raise ValueError(
                f"async weighting must be one of {WEIGHTINGS}, got "
                f"{self.weighting!r}")


def arrival_rates(spec: AsyncSpec, n_devices: int) -> np.ndarray:
    """(N,) float64 per-round completion probabilities r_m.

    Log-spread around the mean rate: ``r_m = arrival_rate * (1+h)^{x_m}``
    with x_m linearly spaced on [-1, 1] — device 0 is the slowest
    straggler, device N-1 the fastest. Deterministic pure NumPy, so both
    backends (and the bound/solver side) share the identical rate bits.
    """
    n = int(n_devices)
    x = np.linspace(-1.0, 1.0, n) if n > 1 else np.zeros(1)
    g = 1.0 + float(spec.rate_heterogeneity)
    return np.clip(float(spec.arrival_rate) * g ** x, RATE_MIN, 1.0)


def staleness_cdf(rates: np.ndarray, buffer_rounds: int) -> np.ndarray:
    """(K, N) float64 staleness CDF thresholds: row j is P(S <= j).

    ``S ~ geometric(r_m)`` (support {0, 1, ...}): ``P(S <= j) =
    1 - (1-r)^{j+1}``. The round loop compares the staleness uniform
    against these *precomputed* thresholds — counting crossed rows gives
    the staleness integer with exact comparisons only (against the
    f32-rounded table), so the
    realization is bit-identical across NumPy/JAX (no in-loop logs whose
    last ulp could differ between libm and XLA). A uniform at or above
    row K-1 means S >= K: the update fell out of the buffer window.
    """
    r = np.asarray(rates, dtype=np.float64)
    j = np.arange(1, int(buffer_rounds) + 1, dtype=np.float64)[:, None]
    return 1.0 - (1.0 - r)[None, :] ** j


def staleness_pmf(rates: np.ndarray, buffer_rounds: int) -> np.ndarray:
    """(K, N) float64 in-window staleness pmf: row s is P(S = s)."""
    cdf = staleness_cdf(rates, buffer_rounds)
    n = cdf.shape[1]
    return np.diff(np.concatenate([np.zeros((1, n)), cdf], axis=0), axis=0)


def delivery_weight(spec: AsyncSpec, n_devices: int) -> np.ndarray:
    """(N,) c_m = E[delta^S ; delivered within the window] per round.

    The static multiplicative tilt the async layer applies to device m's
    participation level: delivery happens with probability r_m, the draw
    stays inside the K-round window with probability P(S < K), and a
    staleness-S payload carries weight delta^S. Computed from the same
    pmf/CDF tables the round loop realizes, so the bound prices exactly
    the simulated process.
    """
    r = arrival_rates(spec, n_devices)
    pmf = staleness_pmf(r, spec.buffer_rounds)
    disc = float(spec.staleness_discount) ** np.arange(int(spec.buffer_rounds))
    return r * np.sum(disc[:, None] * pmf, axis=0)


def expected_staleness(spec: AsyncSpec, n_devices: int) -> np.ndarray:
    """(N,) E[S | delivered within the window] — the solver's per-device
    staleness penalty weight (stale payloads inject drift variance)."""
    r = arrival_rates(spec, n_devices)
    pmf = staleness_pmf(r, spec.buffer_rounds)
    s = np.arange(int(spec.buffer_rounds), dtype=np.float64)
    mass = np.maximum(pmf.sum(axis=0), 1e-300)
    return np.sum(s[:, None] * pmf, axis=0) / mass


@dataclasses.dataclass(frozen=True)
class ResolvedAsync:
    """Validated, backend-shared async configuration (hashable).

    All tables are float64 tuples so the object keys the engine's jitted
    runner cache and compares by content across trainer rebuilds — the
    ``ResolvedParticipation`` pattern.
    """

    buffer_rounds: int           # K — buffer depth / max staleness + 1
    on_missing: str              # "zero" | "stale"
    staleness_discount: float    # delta
    weighting: str               # provenance: "uniform" | "designed"
    rates: tuple                 # (N,) per-round completion probabilities
    weights: tuple               # (N,) PS per-device weights v, sum == N

    @property
    def n_devices(self) -> int:
        return len(self.rates)

    def rates_array(self) -> np.ndarray:
        return np.asarray(self.rates, dtype=np.float64)

    def weights_array(self) -> np.ndarray:
        return np.asarray(self.weights, dtype=np.float64)

    def cdf_array(self) -> np.ndarray:
        """(K, N) staleness CDF thresholds (:func:`staleness_cdf`)."""
        return staleness_cdf(self.rates_array(), self.buffer_rounds)

    def discounts_array(self) -> np.ndarray:
        """(K,) staleness discount table delta^s."""
        return (float(self.staleness_discount)
                ** np.arange(int(self.buffer_rounds), dtype=np.float64))

    def delivery_weight_array(self) -> np.ndarray:
        """(N,) c_m — see :func:`delivery_weight`."""
        r = self.rates_array()
        pmf = staleness_pmf(r, self.buffer_rounds)
        return r * np.sum(self.discounts_array()[:, None] * pmf, axis=0)

    def payload_scale_array(self) -> np.ndarray:
        """(N,) per-device payload scale ``v_m * N / sum(c v)``.

        The global factor normalizes the *expected* delivered mass to N
        (the synchronous all-deliver reference), so async runs stay on the
        trainer's step-size scale and only the per-device tilt — the
        priced bias — differs across weightings.
        """
        c = self.delivery_weight_array()
        v = self.weights_array()
        return v * (self.n_devices / float(np.sum(c * v)))


def resolve(mode: str, spec: Optional[AsyncSpec], n_devices: int,
            weights=None) -> Optional[ResolvedAsync]:
    """Normalize the (mode, spec, weights) knobs both backends take.

    Returns None under ``mode="sync"`` (the strict no-op); otherwise a
    validated :class:`ResolvedAsync`. Explicit ``weights`` override the
    weighting policy's construction (that is how "designed" weights from
    ``sca_jax.solve_async_batch`` reach the trainer); they must lie on
    {sum v = N, v > 0}.
    """
    if mode not in MODES:
        raise ValueError(f"run mode must be one of {MODES}, got {mode!r}")
    if mode == "sync":
        if weights is not None:
            raise ValueError(
                "async_weights given but run mode is 'sync'; set "
                "mode='async' to enable buffered-async aggregation")
        return None
    spec = spec if spec is not None else AsyncSpec()
    n = int(n_devices)
    if weights is not None:
        v = np.asarray(weights, dtype=np.float64)
        if v.shape != (n,):
            raise ValueError(
                f"async_weights must have shape ({n},), got {v.shape}")
        if np.any(v <= 0.0) or not np.all(np.isfinite(v)):
            raise ValueError("async_weights must be finite and > 0")
        if abs(float(v.sum()) - n) > 1e-6 * n:
            raise ValueError(
                f"async_weights must sum to n_devices={n}, got sum "
                f"{float(v.sum()):.9g}")
    elif spec.weighting == "uniform":
        v = np.ones(n)
    else:   # "designed" without explicit weights
        raise ValueError(
            "async weighting='designed' needs explicit async_weights "
            "(solve them with core.sca_jax.solve_async_batch, e.g. via "
            "api.materialize.CellContext.async_weights)")
    return ResolvedAsync(buffer_rounds=int(spec.buffer_rounds),
                         on_missing=spec.on_missing,
                         staleness_discount=float(spec.staleness_discount),
                         weighting=spec.weighting,
                         rates=tuple(arrival_rates(spec, n).tolist()),
                         weights=tuple(v.tolist()))


def _xp(a):
    """Backend namespace sniff: NumPy arrays stay NumPy, everything else
    (jnp arrays and tracers) routes to jnp — the where/concatenate calls
    below are the only ops the two array APIs don't share operator-wise."""
    return np if isinstance(a, np.ndarray) else jnp


def async_round(g, buf, u, rates, cdf, discounts, pay_scale):
    """One buffered-async delivery step, shared by both backends.

    ``g`` (N, d) is the round's fresh per-device gradients (already
    payload-cast and participation-scaled), ``buf`` (K, N, d) the
    staleness buffer (slot s = gradients computed s rounds ago, before
    this round's shift), ``u`` the round's (2, N) f32 ARRIVAL uniforms
    (the oracle widens them to float64, exactly), and ``rates`` (N,) /
    ``cdf`` (K, N) / ``discounts`` (K,) / ``pay_scale`` (N,) the resolved
    tables in the *caller's* backend dtype (the NumPy oracle passes
    float64 ndarrays with rates/CDF rounded through float32, the engine
    f32 jnp constants).

    Returns ``(payload, ok, buf_new)``: the staleness-discounted delivered
    payloads ``delta^S * v * (N/sum(cv)) * g(w_{t-S})``, the (N,) boolean
    delivery mask (False = no completion this round, or the draw fell out
    of the buffer window), and the shifted buffer. The mask and staleness
    come from exact comparisons against the shared f32-rounded tables, so
    the realized mask and staleness integers are bit-identical across
    NumPy/JAX and both rng modes; the payload itself agrees to f32
    round-off.
    """
    xp = _xp(g)
    buf = xp.concatenate([g[None], buf[:-1]], axis=0)
    k = buf.shape[0]
    n = g.shape[0]
    deliver = u[0] < rates
    crossed = (u[1][None, :] >= cdf).sum(axis=0)      # (N,) staleness int
    ok = deliver & (crossed < k)
    s = xp.minimum(crossed, k - 1)
    g_sel = buf[s, xp.arange(n)]
    payload = g_sel * (discounts[s] * pay_scale)[:, None]
    return payload, ok, buf


def stale_replace(g, ok, g_last):
    """Missing payloads replay the last received ones; returns
    ``(g_new, g_last_new)``.

    The single last-gradient code path behind both staleness fallbacks:
    ``fault.on_missing="stale"`` (the PR-8 policy, now routed through
    here) and the async layer's ``on_missing="stale"``. ``ok`` is the
    (N,) boolean delivery mask; the updated carry is the post-replacement
    payload matrix itself (a device's slot always holds the last payload
    the PS actually consumed).
    """
    g_new = _xp(g).where(ok[:, None], g, g_last)
    return g_new, g_new
