"""Pallas TPU kernel: fused per-row (max|g|, sum g^2) reduction.

The digital-FL selection/bit-allocation schemes score every device's
gradient each round: ``||g||_inf`` feeds the quantizer scale and the
quantization-MSE proxy d*||g||_inf^2/(2^r-1)^2 (Lemma 2), ``||g||_2``
drives norm-based scheduling (BestChannel-Norm's top-K and its
bits-proportional-to-norms split). Both are single-pass row reductions
over the same (N, d) gradient block, so one fused HBM->VMEM sweep produces
the (N, 2) statistics instead of two full passes.

Layout matches ``dithered_quant.dithered_quantize_rows_2d``: the caller
flattens/pads each device's gradient to ``r_dev`` rows of 128 lanes and
stacks devices; the grid walks (device, row-block) with the row-block axis
innermost, accumulating into the (1, 2) f32 SMEM output row that every
j-step of device i revisits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .dithered_quant import BLOCK_ROWS, LANES, check_operands, smem_rows


def _kernel(g_ref, o_ref):
    j = pl.program_id(1)
    # widen the payload block before reducing (bf16 payload, f32 stats):
    # a bf16 sum-of-squares saturates after a few hundred terms
    g = g_ref[...].astype(jnp.float32)
    pmax = jnp.max(jnp.abs(g))
    psum = jnp.sum(g * g)

    @pl.when(j == 0)
    def _init():
        o_ref[0, 0] = pmax
        o_ref[0, 1] = psum

    @pl.when(j > 0)
    def _accumulate():
        o_ref[0, 0] = jnp.maximum(o_ref[0, 0], pmax)
        o_ref[0, 1] = o_ref[0, 1] + psum


@functools.partial(jax.jit,
                   static_argnames=("n_dev", "interpret", "block_rows"))
def row_maxabs_sumsq_2d(g2d: jnp.ndarray, n_dev: int = None,
                        interpret: bool = False,
                        block_rows: int = BLOCK_ROWS) -> jnp.ndarray:
    """g2d: (N*R_dev, LANES), device i owning rows [i*R_dev, (i+1)*R_dev).

    Returns (N, 2) f32: column 0 = max|g_i|, column 1 = sum g_i^2 per
    device, accumulated in f32 whatever the payload dtype (f32 or bf16).
    Zero padding is inert for both statistics.
    """
    check_operands(g2d)
    NR = g2d.shape[0]
    r_dev = NR // n_dev
    blocks_per_dev = r_dev // block_rows
    return pl.pallas_call(
        _kernel,
        grid=(n_dev, blocks_per_dev),
        in_specs=[
            pl.BlockSpec((block_rows, LANES),
                         lambda i, j, b=blocks_per_dev: (i * b + j, 0)),
        ],
        out_specs=smem_rows(2, lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_dev, 1, 2), jnp.float32),
        interpret=interpret,
    )(g2d).reshape(n_dev, 2)
