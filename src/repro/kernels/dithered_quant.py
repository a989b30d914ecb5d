"""Pallas TPU kernel: dithered stochastic uniform quantize-dequantize.

The digital-FL payload compressor (paper Sec. II-B). At LM scale the
gradient has 10^7–10^12 entries; quantization is a pure elementwise
streaming op, so the kernel is memory-bound — the win over the naive
composition is fusing (normalize, floor, compare, clip, affine) into one
HBM->VMEM pass instead of five intermediate arrays.

Layout: the caller flattens/pads the tensor to (R, 128) with R a multiple
of the block row count; grid walks row-blocks. The scalar pair
(m = ||g||_inf, levels = 2^r - 1) is an f32 block in SMEM — one (1, 2)
row per device for the batched launch. The payload block may be f32 or
bf16; the arithmetic and the output are in the scalars' f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_ROWS = 512
LANES = 128


def check_operands(*arrays) -> None:
    """Refuse 64-bit operands before they reach a ``pallas_call``: Mosaic
    has no 64-bit vector types, and XLA's x64 rewrite cannot pass through
    a custom call. The device path is f32 (bf16 payloads) by contract."""
    for a in arrays:
        if jnp.dtype(a.dtype).itemsize > 4:
            raise TypeError(
                f"Pallas kernels take 32-bit (or narrower) operands; got "
                f"{a.dtype}. Cast to float32 before the kernel call.")


def smem_rows(width: int, index_map) -> pl.BlockSpec:
    """One (1, width) row of per-device f32 scalars, placed in SMEM.

    The operand is the (N, width) scalar table viewed as (N, 1, width)
    (:func:`as_rows`): a block's last two dims then equal the array's,
    which Mosaic accepts for any N, and the kernel sees a (1, width) ref.
    ``index_map`` returns the (device, 0) block index.
    """
    return pl.BlockSpec((None, 1, width),
                        lambda *ij: (*index_map(*ij), 0),
                        memory_space=pltpu.SMEM)


def as_rows(scal: jnp.ndarray) -> jnp.ndarray:
    """(N, width) scalar table -> the (N, 1, width) view :func:`smem_rows`
    blocks over."""
    return scal.reshape(scal.shape[0], 1, scal.shape[1])


def quantize_codes(g, u, m, levels):
    """Integer-valued codes q in [0, levels] (f32), and the validity flag.

    Degenerate scalars quantize to zero: m == 0 (zero tensor) and
    levels <= 0 (device granted no bits by the selection/bit allocation).
    Shared by the two-step kernel below and the fused pack kernel, so the
    two paths agree operation for operation.
    """
    valid = (levels > 0) & (m > 0)
    safe = jnp.where(valid, 2.0 * m / jnp.where(levels > 0, levels, 1.0), 1.0)
    x = (g + m) / safe
    lo = jnp.floor(x)
    up = (u < (x - lo)).astype(x.dtype)
    return jnp.clip(lo + up, 0.0, levels), safe, valid


def _kernel(scal_ref, g_ref, u_ref, o_ref):
    m = scal_ref[0, 0]
    levels = scal_ref[0, 1]
    g = g_ref[...].astype(o_ref.dtype)
    q, safe, valid = quantize_codes(g, u_ref[...], m, levels)
    out = -m + safe * q
    o_ref[...] = jnp.where(valid, out, jnp.zeros_like(out))


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def dithered_quantize_2d(g2d: jnp.ndarray, u2d: jnp.ndarray,
                         m: jnp.ndarray, levels: jnp.ndarray,
                         interpret: bool = False,
                         block_rows: int = BLOCK_ROWS) -> jnp.ndarray:
    """g2d/u2d: (R, 128) with R % block_rows == 0; m/levels f32 scalars."""
    check_operands(g2d, u2d, m, levels)
    R = g2d.shape[0]
    scal = jnp.stack([m, levels]).astype(jnp.float32).reshape(1, 2)
    return pl.pallas_call(
        _kernel,
        grid=(R // block_rows,),
        in_specs=[
            smem_rows(2, lambda i: (0, 0)),
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(g2d.shape, jnp.float32),
        interpret=interpret,
    )(as_rows(scal), g2d, u2d)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def dithered_quantize_rows_2d(g2d: jnp.ndarray, u2d: jnp.ndarray,
                              scal: jnp.ndarray,
                              interpret: bool = False,
                              block_rows: int = BLOCK_ROWS) -> jnp.ndarray:
    """Batched variant: N independent tensors quantized in one launch.

    g2d/u2d: (N*R_dev, LANES) — device i owns rows [i*R_dev, (i+1)*R_dev);
    scal: (N, 2) f32 per-device (m_i = ||g_i||_inf, levels_i = 2^{r_i} - 1).
    Grid walks (device, row-block); each block reads its device's scalar
    row from SMEM. This is the FL engine's digital uplink: all N devices'
    payloads compress in a single fused pass instead of N kernel launches
    per round.
    """
    check_operands(g2d, u2d, scal)
    NR = g2d.shape[0]
    n_dev = scal.shape[0]
    blocks_per_dev = NR // n_dev // block_rows
    rows = lambda i, j, b=blocks_per_dev: (i * b + j, 0)
    return pl.pallas_call(
        _kernel,
        grid=(n_dev, blocks_per_dev),
        in_specs=[
            smem_rows(2, lambda i, j: (i, 0)),
            pl.BlockSpec((block_rows, LANES), rows),
            pl.BlockSpec((block_rows, LANES), rows),
        ],
        out_specs=pl.BlockSpec((block_rows, LANES), rows),
        out_shape=jax.ShapeDtypeStruct(g2d.shape, scal.dtype),
        interpret=interpret,
    )(as_rows(scal), g2d, u2d)
