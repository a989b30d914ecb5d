"""Dithered stochastic uniform quantization (QSGD-style) — Sec. II-B.

Device m normalizes its gradient by ||g||_inf and quantizes every entry with
r_m bits using a dithered *stochastic uniform* quantizer.  The payload is
``64 + d*r`` bits (norm in fp64 + d quantized entries).

Quantizer (per coordinate x in [-M, M], M = ||g||_inf, s = 2^r - 1 levels):
    Delta = 2*M / s
    q(x)  = -M + Delta * round_stochastic((x + M) / Delta)
Stochastic rounding makes the quantizer unbiased: E[q(x)|x] = x, and the
error variance is bounded by Delta^2/4 per coordinate, i.e.
    var(g_q | g) <= d * ||g||_inf^2 / (2^r - 1)^2,
which is exactly the bound used in Lemma 2.

Two implementations are provided:
- ``quantize_np``   : numpy (FL simulation path, bit-true payload counting)
- ``quantize_jnp``  : jax.numpy (jit-able; used by the distributed digital
                      aggregator and as the kernel oracle in kernels/ref.py)
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp


def payload_bits(d: int, r: int) -> int:
    """L_m = 64 + d*r bits (norm scalar + quantized entries)."""
    return 64 + d * int(r)


def _levels(r_bits: int) -> int:
    return (1 << int(r_bits)) - 1


def quantize_np(g: np.ndarray, r_bits: int, rng: np.random.Generator) -> np.ndarray:
    """Dithered stochastic uniform quantization, numpy reference.

    Draws the dither from ``rng`` (sequential stream). The FL trainer path
    instead supplies counter-based dither explicitly via
    :func:`quantize_np_dither` so the JAX engine can regenerate the same
    stream per round (see ``core.rngstream``).
    """
    g = np.asarray(g, dtype=np.float64)
    if np.max(np.abs(g)) == 0.0 or r_bits <= 0:
        return np.zeros_like(g)
    return quantize_np_dither(g, r_bits, rng.uniform(size=g.shape))


def quantize_np_dither(g: np.ndarray, r_bits: int,
                       u: np.ndarray) -> np.ndarray:
    """Quantize-dequantize with an explicit dither operand ``u`` (g's shape).

    Same arithmetic as :func:`quantize_np`; ``u`` holds the per-entry
    stochastic-rounding uniforms, so callers control the dither stream.
    """
    g = np.asarray(g, dtype=np.float64)
    m = np.max(np.abs(g))
    if m == 0.0 or r_bits <= 0:
        return np.zeros_like(g)
    s = _levels(r_bits)
    delta = 2.0 * m / s
    if delta == 0.0:                         # subnormal m: no grid step
        return np.zeros_like(g)
    x = (g + m) / delta                      # in [0, s]
    lo = np.floor(x)
    frac = x - lo
    up = np.asarray(u, dtype=np.float64) < frac    # stochastic rounding
    q_idx = np.clip(lo + up, 0, s)
    return -m + delta * q_idx


def quantize_jnp(g: jnp.ndarray, r_bits: int, key: jax.Array) -> jnp.ndarray:
    """Dithered stochastic uniform quantization, jax reference (unbiased)."""
    m = jnp.max(jnp.abs(g))
    s = float(_levels(r_bits))
    delta = 2.0 * m / s
    safe_delta = jnp.where(delta > 0, delta, 1.0)
    x = (g + m) / safe_delta
    lo = jnp.floor(x)
    frac = x - lo
    up = (jax.random.uniform(key, g.shape, dtype=g.dtype) < frac).astype(g.dtype)
    q_idx = jnp.clip(lo + up, 0.0, s)
    out = -m + delta * q_idx
    return jnp.where(delta > 0, out, jnp.zeros_like(g))


def quantization_variance_bound(d: int, r_bits: int, g_inf_norm: float) -> float:
    """var(g_q | g) <= d * ||g||_inf^2 / (2^r - 1)^2 (Lemma 2 ingredient)."""
    s = _levels(r_bits)
    return d * (g_inf_norm ** 2) / float(s * s)
