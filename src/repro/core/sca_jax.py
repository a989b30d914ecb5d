"""Batched JAX SCA design solver — Sec. IV on a whole sweep grid at once.

``core/sca.py`` drives one SciPy SLSQP solve per surrogate per anchor —
trusted, but a Python loop per grid point: the paper's sweeps (omega
trade-off grids, SNR points, heterogeneity levels, Monte-Carlo
deployments) multiply 12–15 SLSQP solves by dozens of embarrassingly
parallel design problems. This module solves the *whole grid in one jit*:

  * OTA (15): the exact gamma-only reduction proven out by
    ``design_ota_direct`` — under the simplex constraint (15e), the
    coupling (15b) pins ``alpha = sum_m alpha_m(gamma_m)`` and
    ``p_m = alpha_m/alpha``, so the original objective is a smooth
    box-constrained function of gamma alone. The solver is projected
    Adam with an SCA-style outer ``lax.scan`` of re-anchored stages at
    decreasing step sizes.

  * Digital (17): projected Adam on the reduced variables
    ``(p, beta, r')`` with the latency constraint (17b) folded in as a
    hinge penalty; the outer ``lax.scan`` escalates the penalty weight
    (classic penalty-method SCA analogue). After every stage the iterate
    is projected to *exact* feasibility — simplex projection for ``p``
    and the same raise-thresholds bisection as
    ``digital_design._fit_latency`` — and the true objective (17a) of
    the feasible point is tracked, so the returned solution is always
    feasible and its objective directly comparable to the SciPy oracle.

Everything is float64 (scoped ``jax.enable_x64``) and vmapped over
``anchors × grid points``. A TPU v5e emulates f64 as a pair of f32: about
twice f32's mantissa, but f32's exponent range (1e-300 flushes to zero,
1e300 overflows), so every quantity here must stay inside f32's range;
there the solves agree with the CPU's native f64 to 4e-10
(``chip_smoke.py``). The SciPy path in ``sca.py`` remains the trusted
oracle (``benchmarks/design_bench.py`` records wall-clock and
objective parity).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp


#: Objective parity with the SciPy SCA oracle: on every grid point the
#: batched solver's true objective is within this relative margin of the
#: per-point ``core.sca`` solution, or better (``tests/test_design_batch.py``,
#: ``benchmarks/design_bench.py`` and ``chip_smoke.py`` all hold it to this).
ORACLE_RTOL = 1e-3

# Inner-solver schedule: SCA-style outer stages (re-anchor at the best
# iterate, shrink the step) x Adam steps per stage. The variables are
# pre-scaled to O(1), so the rates are problem-independent.
_OTA_LRS = (0.1, 0.03, 0.01, 0.003)
_OTA_STEPS = 300
# Digital: penalty escalation mu_k with matching step-size decay.
_DIG_MUS = (1.0, 10.0, 100.0, 1e3, 1e4)
_DIG_LRS = (0.05, 0.02, 0.01, 0.005, 0.002)
_DIG_STEPS = 400
# Participation co-design: projected Adam on the capped simplex.
_PART_LRS = (0.1, 0.03, 0.01)
_PART_STEPS = 300
_PART_PI_MIN = 1e-6

_B1, _B2, _ADAM_EPS = 0.9, 0.999, 1e-12


def simplex_projection_jax(v: jnp.ndarray) -> jnp.ndarray:
    """Euclidean projection onto the probability simplex (jit/vmap-able).

    Mirrors ``sca.simplex_projection`` (sort + cumsum threshold rule).
    """
    n = v.shape[0]
    u = jnp.sort(v)[::-1]
    css = jnp.cumsum(u)
    cond = u * jnp.arange(1, n + 1) > (css - 1.0)
    rho = jnp.max(jnp.where(cond, jnp.arange(n), -1))
    theta = (css[rho] - 1.0) / (rho + 1.0)
    return jnp.maximum(v - theta, 0.0)


def _adam_descent(value_and_grad, x0, lo, hi, *, lr, n_steps, track_best):
    """``n_steps`` of Adam projected onto the box [lo, hi] via clipping.

    ``track_best=True`` additionally records the best objective seen at the
    (already clipped) iterates — used where the objective IS the true
    objective (OTA reduction); penalty objectives skip it.
    """
    m0 = jnp.zeros_like(x0)
    v0 = jnp.zeros_like(x0)
    f0 = value_and_grad(x0)[0]

    def step(carry, i):
        x, m, v, bx, bf = carry
        f, g = value_and_grad(x)
        if track_best:
            bx = jnp.where(f < bf, x, bx)
            bf = jnp.minimum(f, bf)
        m = _B1 * m + (1.0 - _B1) * g
        v = _B2 * v + (1.0 - _B2) * g * g
        mhat = m / (1.0 - _B1 ** (i + 1))
        vhat = v / (1.0 - _B2 ** (i + 1))
        x = jnp.clip(x - lr * mhat / (jnp.sqrt(vhat) + _ADAM_EPS), lo, hi)
        return (x, m, v, bx, bf), None

    (x, _, _, bx, bf), _ = jax.lax.scan(
        step, (x0, m0, v0, x0, f0), jnp.arange(n_steps))
    return x, bx, bf


def capped_simplex_projection_jax(v: jnp.ndarray, s, lo=_PART_PI_MIN,
                                  hi=1.0) -> jnp.ndarray:
    """Euclidean projection onto {sum x = s, lo <= x <= hi} (jittable).

    Bisection on the dual shift tau in ``x = clip(v - tau, lo, hi)``: the
    coordinate sum is monotone non-increasing in tau, bracketed by
    [min(v) - hi, max(v) - lo]. A fixed iteration count (no data-dependent
    loop) keeps the projection scan/vmap-friendly; 100 halvings close the
    bracket far below float64 resolution.
    """
    def body(carry, _):
        lo_t, hi_t = carry
        mid = 0.5 * (lo_t + hi_t)
        tot = jnp.sum(jnp.clip(v - mid, lo, hi))
        return (jnp.where(tot > s, mid, lo_t),
                jnp.where(tot > s, hi_t, mid)), None

    bracket = (jnp.min(v) - hi, jnp.max(v) - lo)
    (_, tau), _ = jax.lax.scan(body, bracket, None, length=100)
    return jnp.clip(v - tau, lo, hi)


# -------------------------------------------- participation co-design

def _solve_participation_one(p, q, s, wv, wb):
    """One participation design point: Bernoulli inclusion probs pi.

    Minimizes the bound-shaped objective over the capped simplex
    {sum pi = S, pi_min <= pi <= 1}: with the *effective participation
    levels* ``e = p * pi * q * (N/S)`` — exactly
    ``bounds.effective_participation`` under zero-fill degradation, the
    regime where sampling bias is priced —

        J(pi) = omega_bias * sum (e - 1/N)^2             (priced bias)
              + omega_var  / (sum e)^2                   (noise inflation)

    The variance term is the post-normalization noise proxy of a wireless
    aggregate: the PS noise is per-round and common, so the effective
    noise power after dividing by the delivered signal mass scales as
    1/(sum_m e_m)^2 — a cohort that samples devices the fades starve
    delivers less mass and amplifies noise. The solver therefore trades
    tilting pi toward reliably-delivering devices (throughput / variance)
    against leveling the effective participation at 1/N (bias), the same
    bias-variance structure as (15a)/(17a). Three anchors (uniform,
    proportional to p*q, proportional to sqrt(p*q)) feed projected Adam
    stages at decreasing step sizes; best feasible iterate wins.
    """
    n = p.shape[0]
    w = jnp.maximum(p * q, 1e-30)

    def obj(pi):
        e = (n / s) * w * pi
        return (wb * jnp.sum((e - 1.0 / n) ** 2)
                + wv / jnp.sum(e) ** 2)

    proj = lambda x: capped_simplex_projection_jax(x, s)
    anchors = jnp.stack([
        jnp.full((n,), s / n),
        proj(w * (s / jnp.sum(w))),
        proj(jnp.sqrt(w) * (s / jnp.sum(jnp.sqrt(w)))),
    ])
    vg = jax.value_and_grad(obj)
    scale = 1.0 / jnp.maximum(jnp.abs(obj(anchors[0])), 1e-30)

    def run_anchor(x0):
        def stage(carry, lr):
            x, bx, bf = carry

            def step(inner, i):
                x, m, v = inner
                f, g = vg(x)
                g = g * scale
                m = _B1 * m + (1.0 - _B1) * g
                v = _B2 * v + (1.0 - _B2) * g * g
                mhat = m / (1.0 - _B1 ** (i + 1))
                vhat = v / (1.0 - _B2 ** (i + 1))
                x = proj(x - lr * mhat / (jnp.sqrt(vhat) + _ADAM_EPS))
                return (x, m, v), None

            (x, _, _), _ = jax.lax.scan(
                step, (x, jnp.zeros_like(x), jnp.zeros_like(x)),
                jnp.arange(_PART_STEPS))
            f = obj(x)
            bx = jnp.where(f < bf, x, bx)
            bf = jnp.minimum(f, bf)
            return (bx, bx, bf), None           # re-anchor at the best

        (_, bx, bf), _ = jax.lax.scan(stage, (x0, x0, obj(x0)),
                                      jnp.asarray(_PART_LRS))
        return bx, bf

    bxs, bfs = jax.vmap(run_anchor)(anchors)
    i = jnp.argmin(bfs)
    return bxs[i], bfs[i]


@functools.lru_cache(maxsize=None)
def _participation_solver_jit():
    return jax.jit(jax.vmap(_solve_participation_one))


def solve_participation_batch(p, q, clients, omega_var, omega_bias):
    """Solve a batch of participation co-design problems in one jit.

    Args (leading batch axis B; N devices): p (B, N) effective scheme
    participation levels, q (B, N) fault survival probabilities (ones when
    faults are off), clients (B,) expected cohort sizes S, omega_var /
    omega_bias (B,) the cell's bound weights.

    Returns:
      (pi, objectives): (B, N) float64 inclusion probabilities on the
      capped simplex {sum pi = S, pi <= 1} and (B,) objective values.
    """
    with jax.enable_x64(True):
        args = [jnp.asarray(np.asarray(a, dtype=np.float64))
                for a in (p, q, clients, omega_var, omega_bias)]
        pi, obj = _participation_solver_jit()(*args)
        return np.asarray(pi), np.asarray(obj)


# ---------------------------------------------------- async co-design

def _solve_async_one(p, c, sbar, wv, wb):
    """One buffered-async design point: PS per-device weights v.

    Minimizes the bound-shaped objective over {sum v = N,
    v_min <= v <= N}: with the *async effective participation levels*
    ``e = p * c * v * (N / sum(c v))`` — exactly
    ``bounds.async_effective_participation``, where ``c`` is the per-device
    staleness-discounted delivery weight
    (``core.async_fl.delivery_weight``) —

        J(v) = omega_bias * sum (e - 1/N)^2            (priced stale bias)
             + omega_var * (1/(sum e)^2               (noise inflation)
                            + sum e^2 * sbar)         (staleness drift)

    The first variance piece is the participation solver's delivered-mass
    noise proxy; the second weights each device's squared effective level
    by its expected staleness ``sbar_m`` (E[S | delivered],
    ``core.async_fl.expected_staleness``) — a staleness-S gradient drifts
    from the fresh one by O(S) optimization progress, so leaning on
    chronically-stale devices injects drift variance. The solver therefore
    trades up-weighting slow devices (leveling e at 1/N — killing the
    structured staleness bias) against the drift noise of doing so, the
    same bias-variance structure as (15a)/(17a). Three anchors (uniform,
    inverse delivery weight, inverse expected staleness) feed projected
    Adam stages at decreasing step sizes; best feasible iterate wins.
    """
    n = p.shape[0]
    cw = jnp.maximum(c, 1e-30)

    def obj(v):
        e = p * cw * v * (n / jnp.sum(cw * v))
        return (wb * jnp.sum((e - 1.0 / n) ** 2)
                + wv * (1.0 / jnp.sum(e) ** 2 + jnp.sum(e ** 2 * sbar)))

    proj = lambda x: capped_simplex_projection_jax(x, 1.0 * n, hi=1.0 * n)
    inv_c = 1.0 / cw
    inv_s = 1.0 / (1.0 + sbar)
    anchors = jnp.stack([
        jnp.ones((n,)),
        proj(inv_c * (n / jnp.sum(inv_c))),
        proj(inv_s * (n / jnp.sum(inv_s))),
    ])
    vg = jax.value_and_grad(obj)
    scale = 1.0 / jnp.maximum(jnp.abs(obj(anchors[0])), 1e-30)

    def run_anchor(x0):
        def stage(carry, lr):
            x, bx, bf = carry

            def step(inner, i):
                x, m, v = inner
                f, g = vg(x)
                g = g * scale
                m = _B1 * m + (1.0 - _B1) * g
                v = _B2 * v + (1.0 - _B2) * g * g
                mhat = m / (1.0 - _B1 ** (i + 1))
                vhat = v / (1.0 - _B2 ** (i + 1))
                x = proj(x - lr * mhat / (jnp.sqrt(vhat) + _ADAM_EPS))
                return (x, m, v), None

            (x, _, _), _ = jax.lax.scan(
                step, (x, jnp.zeros_like(x), jnp.zeros_like(x)),
                jnp.arange(_PART_STEPS))
            f = obj(x)
            bx = jnp.where(f < bf, x, bx)
            bf = jnp.minimum(f, bf)
            return (bx, bx, bf), None           # re-anchor at the best

        (_, bx, bf), _ = jax.lax.scan(stage, (x0, x0, obj(x0)),
                                      jnp.asarray(_PART_LRS))
        return bx, bf

    bxs, bfs = jax.vmap(run_anchor)(anchors)
    i = jnp.argmin(bfs)
    return bxs[i], bfs[i]


@functools.lru_cache(maxsize=None)
def _async_solver_jit():
    return jax.jit(jax.vmap(_solve_async_one))


def solve_async_batch(p, c, sbar, omega_var, omega_bias):
    """Solve a batch of buffered-async weight design problems in one jit.

    Args (leading batch axis B; N devices): p (B, N) effective scheme
    participation levels (fault/sampling tilts folded in), c (B, N) async
    delivery weights (``core.async_fl.delivery_weight``), sbar (B, N)
    expected staleness (``core.async_fl.expected_staleness``), omega_var /
    omega_bias (B,) the cell's bound weights.

    Returns:
      (v, objectives): (B, N) float64 PS per-device weights on
      {sum v = N, v <= N} and (B,) objective values.
    """
    with jax.enable_x64(True):
        args = [jnp.asarray(np.asarray(a, dtype=np.float64))
                for a in (p, c, sbar, omega_var, omega_bias)]
        v, obj = _async_solver_jit()(*args)
        return np.asarray(v), np.asarray(obj)


# ------------------------------------------------------------- OTA (15)

def _solve_ota_one(lambdas, dim, g_max, e_s, n0, wv, wb, s2, anchors):
    """One OTA design point, all anchors: gamma-reduced objective (15a)."""
    n = lambdas.shape[0]
    c = g_max ** 2 / (dim * lambdas * e_s)
    gmax = jnp.sqrt(lambdas * dim * e_s / (2.0 * g_max ** 2))
    u_g = jnp.median(gmax)                       # O(1) scaling, as the oracle
    g2 = g_max ** 2
    lo, hi = 1e-6, gmax / u_g

    def obj(gs):
        gam = gs * u_g
        x = c * gam ** 2
        a = gam * jnp.exp(-x)
        alpha = jnp.sum(a)
        p = a / alpha
        # exp clip mirrors true_objective_from_gamma's overflow guard
        trans = jnp.sum(p ** 2 * g2 * (jnp.exp(jnp.minimum(x, 700.0)) - 1.0))
        noise = dim * n0 / alpha ** 2
        return (wv * (trans + jnp.sum(p ** 2 * s2) + noise)
                + wb * jnp.sum((p - 1.0 / n) ** 2))

    vg = jax.value_and_grad(obj)
    scale = 1.0 / jnp.maximum(jnp.abs(obj(jnp.clip(
        anchors[0] / u_g, lo, hi))), 1e-30)

    def scaled_vg(x):
        f, g = vg(x)
        return f, g * scale                      # scale-free Adam steps

    def run_anchor(a0):
        x0 = jnp.clip(a0 / u_g, lo, hi)

        def stage(carry, lr):
            x, bx, bf = carry
            _, sbx, sbf = _adam_descent(scaled_vg, x, lo, hi, lr=lr,
                                        n_steps=_OTA_STEPS, track_best=True)
            bx = jnp.where(sbf < bf, sbx, bx)
            bf = jnp.minimum(sbf, bf)
            return (bx, bx, bf), None            # re-anchor at the best

        (_, bx, bf), _ = jax.lax.scan(stage, (x0, x0, obj(x0)),
                                      jnp.asarray(_OTA_LRS))
        return bx, bf

    bxs, bfs = jax.vmap(run_anchor)(anchors)
    i = jnp.argmin(bfs)
    return bxs[i] * u_g, bfs[i]


@functools.lru_cache(maxsize=None)
def _ota_solver_jit():
    return jax.jit(jax.vmap(_solve_ota_one))


def solve_ota_gamma_batch(lambdas, dim, g_max, e_s, n0, omega_var,
                          omega_bias, sigma_sq, anchors):
    """Solve a batch of OTA design problems (15) in one jit.

    Args (leading batch axis B everywhere; N devices, A anchors):
      lambdas (B, N), dim/g_max/e_s/n0/omega_var/omega_bias (B,),
      sigma_sq (B, N), anchors (B, A, N) gamma starting points.

    Returns:
      (gammas, objectives): (B, N) float64 designed pre-scalers and (B,)
      true objectives (15a) at the physically-coupled points.
    """
    with jax.enable_x64(True):
        args = [jnp.asarray(np.asarray(a, dtype=np.float64))
                for a in (lambdas, dim, g_max, e_s, n0, omega_var,
                          omega_bias, sigma_sq, anchors)]
        gam, obj = _ota_solver_jit()(*args)
        return np.asarray(gam), np.asarray(obj)


# --------------------------------------------------------- digital (17)

def _solve_digital_one(lambdas, dim, g_max, e_s, n0, bw, t_max, r_max,
                       wv, wb, s2, anchors):
    """One digital design point, all anchors: reduced (p, beta, r')."""
    n = lambdas.shape[0]
    g2 = g_max ** 2
    snr_gain = lambdas * e_s / n0

    def latency(nlb_s, r):
        """Expected latency (12) from nlb_s = -ln(beta_s) (rho^2/Lambda)."""
        rate = jnp.maximum(jnp.log2(1.0 + snr_gain * nlb_s), 1e-9)
        payload = 64.0 + dim * (r + 1.0)
        return jnp.sum(jnp.exp(-nlb_s) * payload / (bw * rate))

    def fit_latency(beta, r):
        """Raise thresholds (beta -> beta**s) until (17b) holds.

        Same monotone bisection as ``digital_design._fit_latency``, on the
        log scale nlb = -ln(beta) so beta**s never over/underflows.
        """
        nlb = -jnp.log(jnp.clip(beta, 1e-300, 1.0))
        feasible = latency(nlb, r) <= t_max

        def cond(carry):
            lo_s, hi_s = carry
            return (hi_s - lo_s) > 1e-12 * hi_s

        def body(carry):
            lo_s, hi_s = carry
            mid = 0.5 * (lo_s + hi_s)
            bad = latency(mid * nlb, r) > t_max
            return jnp.where(bad, mid, lo_s), jnp.where(bad, hi_s, mid)

        _, hi_s = jax.lax.while_loop(cond, body, (1.0, 1e6))
        s = jnp.where(feasible, 1.0, hi_s)       # oracle keeps the hi end
        return jnp.exp(-s * nlb)

    def true_obj(p, beta, r):
        """(17a) at integer-relaxed bits r = r'+1 (= oracle convention)."""
        s = (2.0 ** (r + 1.0) - 1.0) ** 2
        zeta = (jnp.sum(p ** 2 * g2 * (1.0 / beta - 1.0 + dim / (beta * s)))
                + jnp.sum(p ** 2 * s2))
        return wv * zeta + wb * jnp.sum((p - 1.0 / n) ** 2)

    def split(x):
        return x[:n], x[n:2 * n], x[2 * n:]

    def project(x):
        """Exact feasibility: simplex p, latency-fitted beta, boxed r."""
        p, beta, r = split(x)
        p = simplex_projection_jax(jnp.clip(p, 1e-8, 1.0))
        p = jnp.clip(p, 1e-10, 1.0)
        p = p / jnp.sum(p)
        r = jnp.clip(r, 0.5, r_max - 1.0)
        beta = fit_latency(jnp.clip(beta, 1e-9, 1.0 - 1e-9), r)
        return jnp.concatenate([p, beta, r])

    lo = jnp.concatenate([jnp.full(n, 1e-8), jnp.full(n, 1e-6),
                          jnp.full(n, 0.5)])
    hi = jnp.concatenate([jnp.ones(n), jnp.full(n, 1.0 - 1e-9),
                          jnp.full(n, r_max - 1.0)])

    def run_anchor(x0):
        x0 = project(jnp.clip(x0, lo, hi))
        p0, b0, r0 = split(x0)
        f0 = true_obj(p0, b0, r0)
        scale = 1.0 / jnp.maximum(jnp.abs(f0), 1e-30)

        def pen_obj(x, mu):
            p, beta, r = split(x)
            beta = jnp.clip(beta, 1e-9, 1.0 - 1e-9)
            hinge = jnp.maximum(
                latency(-jnp.log(beta), r) / t_max - 1.0, 0.0)
            psum = jnp.sum(p) - 1.0
            return (scale * true_obj(p, beta, r)
                    + mu * (hinge ** 2 + psum ** 2))

        def stage(carry, stage_args):
            mu, lr = stage_args
            x, bx, bf = carry
            vg = jax.value_and_grad(lambda y: pen_obj(y, mu))
            x, _, _ = _adam_descent(vg, x, lo, hi, lr=lr,
                                    n_steps=_DIG_STEPS, track_best=False)
            xp = project(x)
            f = true_obj(*split(xp))
            bx = jnp.where(f < bf, xp, bx)
            bf = jnp.minimum(f, bf)
            return (xp, bx, bf), None

        (_, bx, bf), _ = jax.lax.scan(
            stage, (x0, x0, f0),
            (jnp.asarray(_DIG_MUS), jnp.asarray(_DIG_LRS)))
        return bx, bf

    bxs, bfs = jax.vmap(run_anchor)(anchors)
    i = jnp.argmin(bfs)
    return bxs[i], bfs[i]


@functools.lru_cache(maxsize=None)
def _digital_solver_jit():
    return jax.jit(jax.vmap(_solve_digital_one))


def solve_digital_batch(lambdas, dim, g_max, e_s, n0, bandwidth_hz, t_max_s,
                        r_max, omega_var, omega_bias, sigma_sq, anchors):
    """Solve a batch of digital design problems (17) in one jit.

    Args (leading batch axis B; N devices, A anchors): lambdas (B, N),
    scalars (B,), sigma_sq (B, N), anchors (B, A, 3N) packed (p, beta, r').

    Returns:
      (x, objectives): (B, 3N) feasible packed solutions and (B,) true
      objectives (17a) at the continuous (integer-relaxed) points —
      directly comparable to ``design_digital_sca``'s ``SCAResult.objective``.
    """
    with jax.enable_x64(True):
        args = [jnp.asarray(np.asarray(a, dtype=np.float64))
                for a in (lambdas, dim, g_max, e_s, n0, bandwidth_hz,
                          t_max_s, r_max, omega_var, omega_bias, sigma_sq,
                          anchors)]
        x, obj = _digital_solver_jit()(*args)
        return np.asarray(x), np.asarray(obj)
