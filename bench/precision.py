"""Matrix products of the plain references, at a named precision.

``highest`` is a full float32 product. ``high`` is the TPU's three-pass
bfloat16 product, written out (each operand split into a bfloat16 high part
and a bfloat16 remainder; the remainder x remainder term dropped), so that
the control computes the same thing on the chip and on the CPU. The split
rounds with ``lax.reduce_precision``: a float32 -> bfloat16 -> float32
round trip written with ``astype`` is removed by XLA's simplifier on the
TPU (excess precision is allowed), which would leave one bfloat16 pass.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "high")


def _bf16(a):
    """``a`` rounded to bfloat16 values, still float32."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _split(a):
    hi = _bf16(a)
    lo = _bf16(a - hi)
    return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)


def einsum(spec: str, a, b, precision: str = "highest"):
    """``jnp.einsum(spec, a, b)`` on float32 operands at ``precision``."""
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    if precision != "high":
        raise ValueError(f"precision must be one of {PRECISIONS}")
    (ah, al), (bh, bl) = _split(a), _split(b)

    def one(x, y):
        return jnp.einsum(spec, x, y, preferred_element_type=jnp.float32)
    return one(ah, bh) + (one(ah, bl) + one(al, bh))
