"""Wireless fault model: outages, erasures, and stragglers as priced bias.

The paper designs a *structured, time-invariant bias* and prices it with
the Theorem-1/2 optimality-error bound; this module supplies the fault
layer that makes the pricing bite. Each round, each device independently

  * **drops out** with probability ``dropout_prob`` (device-side failure:
    compute crash, battery, backhaul loss),
  * suffers a payload **erasure** with probability ``erasure_prob``
    (decoding failure after transmission — latency is still paid),
  * hits a **deep fade** when ``|h_{m,t}| < deep_fade_thresh`` (the
    channel outage the digital threshold rule eq. (9) normally excludes),
  * becomes a **straggler** with probability ``straggler_prob``: its
    uplink takes ``straggler_mult``x longer. With a round deadline
    (``deadline_s``) the straggler's payload misses the round (and the
    round latency is capped at the deadline); without one, the round
    stretches to the straggler's finish time.

The draws are counter-based threefry streams (``core.rngstream.FAULT_TAG``)
— pure functions of ``(seed, trial, round)`` — so both simulation backends
and both RNG execution modes (``rng="replay"``/``"fast"``) see the exact
same fault realizations, bit for bit.

A device that misses the round is handled by the ``on_missing`` policy at
aggregation (implemented gradient-side in ``fl/engine.py`` and
``fl/trainer.py``, upstream of every scheme's combiner so all registered
schemes inherit it):

  * ``"reweight"`` — inverse-propensity weighting: surviving gradients are
    scaled by ``1/q_m`` with ``q_m`` the static survival probability
    (:func:`survival_prob`). Unbiased in expectation (the fault layer adds
    variance, not bias): effective participation stays ``p_m``.
  * ``"zero"`` — the missing payload is zero-filled. The update shrinks
    toward 0 and the effective participation becomes ``p_m * q_m`` — a
    *structured participation bias* the Sec.-IV bound prices via
    ``bounds.effective_participation`` / ``bounds.bias_sum``.
  * ``"stale"`` — the PS reuses the device's last received gradient
    (staleness-as-bias): same participation level, but a time-correlated
    gradient bias the bound does not model — the empirical comparison
    point. Both backends route the replay through the single
    last-gradient code path ``core.async_fl.stale_replace``, shared with
    the buffered-async subsystem that generalizes this policy to a
    last-K staleness buffer with a *priced* stationary staleness
    distribution (``run.mode="async"``, ``core.async_fl``).

Faulted devices keep their reserved TDMA slots / OTA symbols, so
scheme-side latency accounting is unchanged (erasures pay for airtime
they waste); only straggler slowdown and deadline capping modify the
realized round latency.

``FaultSpec`` defaults are a strict no-op: with every knob at its default
both backends take their exact pre-fault code paths, so trajectories are
bit-identical to a build without this module.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from .channel import participation_probability
from .digital import outage_mask

_POLICIES = ("reweight", "zero", "stale")


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """Declarative wireless fault model (pure data, sweepable by axis).

    All probabilities are per device per round, i.i.d. across both.
    """

    dropout_prob: float = 0.0        # device silently absent this round
    erasure_prob: float = 0.0        # payload transmitted but undecodable
    deep_fade_thresh: float = 0.0    # |h| < thresh -> channel outage
    straggler_prob: float = 0.0      # device uplink slowed this round
    straggler_mult: float = 1.0      # straggler slowdown factor (>= 1)
    deadline_s: Optional[float] = None   # round deadline: stragglers miss
    on_missing: str = "reweight"     # "reweight" | "zero" | "stale"

    def __post_init__(self):
        for f in ("dropout_prob", "erasure_prob", "straggler_prob"):
            v = getattr(self, f)
            if not 0.0 <= float(v) <= 1.0:
                raise ValueError(f"fault.{f} must be in [0, 1], got {v!r}")
        if self.deep_fade_thresh < 0.0:
            raise ValueError("fault.deep_fade_thresh must be >= 0, got "
                             f"{self.deep_fade_thresh!r}")
        if self.straggler_mult < 1.0:
            raise ValueError("fault.straggler_mult must be >= 1, got "
                             f"{self.straggler_mult!r}")
        if self.deadline_s is not None and self.deadline_s <= 0.0:
            raise ValueError("fault.deadline_s must be positive or None, "
                             f"got {self.deadline_s!r}")
        if self.on_missing not in _POLICIES:
            raise ValueError(f"fault.on_missing must be one of {_POLICIES}, "
                             f"got {self.on_missing!r}")

    @property
    def enabled(self) -> bool:
        """True iff any knob can change a trajectory. ``straggler_mult``
        alone is inert (it scales the latency of stragglers that never
        occur), preserving the strict-no-op contract for defaults."""
        return (self.dropout_prob > 0.0 or self.erasure_prob > 0.0
                or self.deep_fade_thresh > 0.0 or self.straggler_prob > 0.0
                or self.deadline_s is not None)


def survival_prob(fault: FaultSpec, lambdas: np.ndarray) -> np.ndarray:
    """(N,) per-device round-survival probability q_m.

    Independent fault components compose multiplicatively:
    ``(1 - dropout)(1 - erasure) * P(|h| >= t_f)`` with the Rayleigh
    deep-fade survival ``exp(-t_f^2/Lambda_m)``; under a deadline,
    stragglers also miss, contributing ``(1 - straggler_prob)``. This is
    the static propensity the "reweight" policy inverts and the
    participation factor ``bounds.effective_participation`` prices.
    Floored at 1e-12 so inverse-propensity weights stay finite.
    """
    q = (1.0 - fault.dropout_prob) * (1.0 - fault.erasure_prob)
    q = q * participation_probability(fault.deep_fade_thresh,
                                      np.asarray(lambdas, np.float64))
    if fault.deadline_s is not None:
        q = q * (1.0 - fault.straggler_prob)
    return np.maximum(q, 1e-12)


def effective_lambdas(lambdas: np.ndarray, fault: FaultSpec) -> np.ndarray:
    """Outage-adjusted average channel energies for fault-aware design.

    The design solvers consume statistical CSI {Lambda_m}; under the fault
    layer the energy a device actually *delivers* per round is
    ``E[|h|^2 1{survives}] = q_u (Lambda + t_f^2) exp(-t_f^2/Lambda)``
    (the deep-fade-truncated exponential mean, scaled by the channel-
    independent survival factor q_u). Feeding these into
    ``CellContext.design_spec`` makes the Sec.-IV solves fault-aware
    without touching the solvers. Exactly ``lambdas`` when faults are
    disabled (the strict-no-op contract). Floored at ``1e-12 * Lambda_m``
    so a fade threshold far above a device's channel scale (survival
    underflows to 0) still hands the solvers finite, positive energies —
    the design then just prices that device out.
    """
    lam = np.asarray(lambdas, np.float64)
    if not fault.enabled:
        return lam
    tf2 = float(fault.deep_fade_thresh) ** 2
    q_u = (1.0 - fault.dropout_prob) * (1.0 - fault.erasure_prob)
    if fault.deadline_s is not None:
        q_u = q_u * (1.0 - fault.straggler_prob)
    return np.maximum(q_u * (lam + tf2) * np.exp(-tf2 / lam), 1e-12 * lam)


def fault_masks(u, habs, fault: FaultSpec):
    """Per-round delivery masks from one (3, N) uniform block.

    ``u`` rows are the FAULT-stream uniforms (dropout, erasure, straggler
    — see ``rngstream.fault_block``); ``habs`` the round's |h|. Written
    with operators only, so it runs identically on numpy arrays (oracle)
    and traced jnp arrays (engine scan) — the cross-backend parity point.

    Returns ``(ok, straggler)`` boolean (N,) masks: ``ok`` marks devices
    whose payload reaches the PS this round (deep fades route through the
    same ``digital.outage_mask`` primitive as the threshold rule eq. (9),
    so injected outages and scheme-side in-allocation rules compose in
    one place); ``straggler`` marks slowed devices (they only miss the
    round when a deadline is set).
    """
    # probabilities rounded to f32 for both backends: the f32 engine and
    # the f64 oracle then make the identical comparison on the f32 draws
    dropped = u[0] < np.float32(fault.dropout_prob)
    erased = u[1] < np.float32(fault.erasure_prob)
    straggler = u[2] < np.float32(fault.straggler_prob)
    faded = ~outage_mask(habs, 0.0, deep_fade_thresh=fault.deep_fade_thresh)
    missed = dropped | erased | faded
    if fault.deadline_s is not None:
        missed = missed | straggler
    return ~missed, straggler
