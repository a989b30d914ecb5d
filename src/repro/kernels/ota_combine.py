"""Pallas TPU kernel: fused OTA post-scale + AWGN injection (eq. (6)).

After the ICI all-reduce produces sum_m chi_m gamma_m g_m, the PS epilogue
is ghat = sum/alpha + z/alpha. Fusing the scale and the noise add keeps the
reduced gradient in one HBM->VMEM pass (memory-bound epilogue); the noise
tile is an explicit operand (see kernels/ref.py for why).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .dithered_quant import (BLOCK_ROWS, LANES, as_rows, check_operands,
                             smem_rows)


def _kernel(scal_ref, g_ref, z_ref, o_ref):
    inv_alpha = scal_ref[0, 0]
    # the payload block may be narrower than the accumulator (bf16
    # payload, f32 accumulation): widen per-block before the arithmetic
    out = (g_ref[...].astype(jnp.float32) * inv_alpha
           + z_ref[...].astype(jnp.float32))
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "block_rows", "acc_dtype"))
def ota_combine_2d(g2d: jnp.ndarray, z2d: jnp.ndarray,
                   inv_alpha: jnp.ndarray,
                   interpret: bool = False,
                   block_rows: int = BLOCK_ROWS,
                   acc_dtype=None) -> jnp.ndarray:
    """g2d/z2d: (R,128), R % block_rows == 0; z pre-scaled noise.

    ``block_rows`` tiles the grid; small tensors should pass a small tile
    (interpret-mode cost scales with the padded block, not the payload).
    ``acc_dtype`` sets the accumulate/output dtype when it should be wider
    than the payload dtype (mixed-precision uplink: g2d in bf16, z2d and
    the result in f32); the payload stays narrow in HBM and widens
    per-block in VMEM. Default: g2d.dtype (unchanged legacy behavior).
    """
    check_operands(g2d, z2d, inv_alpha)
    R = g2d.shape[0]
    out_dtype = jnp.dtype(acc_dtype) if acc_dtype is not None else g2d.dtype
    # SMEM holds 32-bit scalars only, whatever the payload dtype
    scal = inv_alpha.astype(jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        _kernel,
        grid=(R // block_rows,),
        in_specs=[
            smem_rows(1, lambda i: (0, 0)),
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(g2d.shape, out_dtype),
        interpret=interpret,
    )(as_rows(scal), g2d, z2d.astype(out_dtype))
