"""Pallas TPU kernels: fused digital-payload pipeline at gradient scale.

The digital uplink's hot path used to be two full passes over (N, d):
quantize-dequantize every device's gradient (materializing the f32/f64
dequantized block), then a weighted reduction. At payload scale
(d = 10^5–10^7) that block is the dominant memory term — N=256 devices at
d=10^6 is a 1 GB f32 tensor that exists only to be summed.

Three kernels replace it:

  ``quantize_pack_rows_2d``   dither → quantize → bit-pack codes into a
                              uint32 payload buffer (K = 32/code_bits
                              codes per word), one pass per device block.
                              This *is* the wire format: r-bit codes, not
                              dequantized floats, so the payload buffer is
                              code_bits/32 the size of the float block.
  ``packed_weighted_sum_2d``  unpack → dequantize → weighted-accumulate
                              into an O(d) accumulator. The grid walks
                              (row-block, device) with the DEVICE axis
                              innermost, so each output block is revisited
                              across devices in index order — the same
                              sequential order as the NumPy oracle's
                              ``acc += chi_m/nu_m * gq_m`` loop, which
                              keeps the fused path aligned with the
                              reference scan to the last ulp (XLA FMA
                              contraction is the only divergence). The
                              dequantized (N, d) tensor is never
                              materialized.
  ``unpack_dequant_rows_2d``  unpack → dequantize, materializing the
                              (N*R, LANES) float block — the
                              "materialize-then-sum" baseline the bench
                              compares against, and the payload decoder
                              for anything that wants per-device floats.

Packing layout: codes are integers in [0, levels] with levels <= 2^16 - 1
(static ``code_bits`` in {4, 8, 16}). A (block_rows, LANES) code block
splits into K = 32/code_bits contiguous row chunks of block_rows/K rows;
chunk k fills bits [k*code_bits, (k+1)*code_bits) of the
(block_rows/K, LANES) word block. Every chunk is a whole number of
sublane tiles (block_rows/K is a multiple of 8), so packing is
slice-shift-or on aligned tiles. Codes survive the float round-trip
exactly (f32 represents all integers < 2^24), so pack → unpack →
dequantize reproduces the two-step quantizer bit-for-bit. Float codes
convert to words through int32 (codes are non-negative and < 2^16, so the
int32 → uint32 step is exact).

Quantizer arithmetic is ``dithered_quant.quantize_codes``, the two-step
kernel's own; the ``levels <= 0`` / ``m == 0`` degenerate rows (devices
granted no bits) pack to code 0 and dequantize to exact 0. The per-device
scalars are f32 rows in SMEM; payload blocks may be f32 or bf16 and are
widened to f32 in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dithered_quant import (BLOCK_ROWS, LANES, as_rows, check_operands,
                             quantize_codes, smem_rows)

CODE_BITS_CHOICES = (4, 8, 16)


def min_block_rows(code_bits: int) -> int:
    """Smallest block whose K packed chunks are whole (8, 128) tiles."""
    return 8 * (32 // code_bits)


def _pack_words(q_u32, code_bits):
    """(br, LANES) uint32 codes -> (br/K, LANES) packed words."""
    K = 32 // code_bits
    rows = q_u32.shape[0] // K
    word = q_u32[:rows]
    for k in range(1, K):
        word = word | (q_u32[k * rows:(k + 1) * rows] << (k * code_bits))
    return word


def _unpack_words(word, code_bits):
    """(brp, LANES) packed words -> (brp*K, LANES) uint32 codes."""
    K = 32 // code_bits
    if K == 1:
        return word
    mask = jnp.uint32((1 << code_bits) - 1)
    return jnp.concatenate(
        [(word >> (k * code_bits)) & mask for k in range(K)], axis=0)


def _unpack_blocks(word, code_bits, block_rows):
    """:func:`_unpack_words` over a run of whole ``block_rows`` blocks,
    each packed on its own (the device-blocked interpret-mode launch)."""
    lanes = word.shape[-1]
    blocks = word.reshape(-1, block_rows // (32 // code_bits), lanes)
    return jax.vmap(lambda w: _unpack_words(w, code_bits))(
        blocks).reshape(-1, lanes)


def _dequant(q_u32, m, levels):
    """Codes -> f32 values: -m + (2m/levels) * q, degenerate rows -> 0."""
    qf = q_u32.astype(jnp.int32).astype(jnp.float32)
    valid = (levels > 0) & (m > 0)
    safe = jnp.where(valid, 2.0 * m / jnp.where(levels > 0, levels, 1.0), 1.0)
    return jnp.where(valid, -m + safe * qf, jnp.zeros_like(qf))


def _pack_kernel(scal_ref, g_ref, u_ref, o_ref, *, code_bits):
    m = scal_ref[0, 0]
    levels = scal_ref[0, 1]
    q, _, valid = quantize_codes(g_ref[...].astype(jnp.float32), u_ref[...],
                                 m, levels)
    q = jnp.where(valid, q, jnp.zeros_like(q))
    o_ref[...] = _pack_words(q.astype(jnp.int32).astype(jnp.uint32),
                             code_bits)


def _unpack_kernel(scal_ref, p_ref, o_ref, *, code_bits):
    m = scal_ref[0, 0]
    levels = scal_ref[0, 1]
    o_ref[...] = _dequant(_unpack_words(p_ref[...], code_bits), m, levels)


def _wsum_kernel(scal_ref, p_ref, o_ref, *, code_bits):
    dev = pl.program_id(1)
    m = scal_ref[0, 0]
    levels = scal_ref[0, 1]
    w = scal_ref[0, 2]
    contrib = w * _dequant(_unpack_words(p_ref[...], code_bits), m, levels)

    @pl.when(dev == 0)
    def _init():
        o_ref[...] = contrib

    @pl.when(dev > 0)
    def _accumulate():
        o_ref[...] = o_ref[...] + contrib


def _wsum_devblock_kernel(scal_ref, p_ref, o_ref, *, code_bits, dev_block,
                          rp_words, block_rows):
    """Device-blocked variant: one grid step accumulates ``dev_block``
    whole device payloads (``rp_words`` packed rows each). Grid-step
    overhead dominates the revisited-accumulator pattern (in interpret
    mode every step copies the full operand buffers), so fewer, fatter
    steps win; the inner loop still adds devices one at a time in index
    order, preserving the oracle's sequential association."""
    mb = pl.program_id(0)

    @pl.when(mb == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref[...])

    for k in range(dev_block):
        m = scal_ref[k, 0]
        levels = scal_ref[k, 1]
        w = scal_ref[k, 2]
        q = _unpack_blocks(p_ref[k * rp_words:(k + 1) * rp_words, :],
                           code_bits, block_rows)
        o_ref[...] = o_ref[...] + w * _dequant(q, m, levels)


@functools.partial(jax.jit,
                   static_argnames=("code_bits", "interpret", "block_rows"))
def quantize_pack_rows_2d(g2d: jnp.ndarray, u2d: jnp.ndarray,
                          scal: jnp.ndarray, code_bits: int,
                          interpret: bool = False,
                          block_rows: int = BLOCK_ROWS) -> jnp.ndarray:
    """Fused dither-quantize-pack over N stacked device payloads.

    g2d/u2d: (N*R_dev, LANES) — device i owns rows [i*R_dev, (i+1)*R_dev);
    scal: (N, 2) per-device (m_i, levels_i) with levels_i <= 2^code_bits-1.
    Returns (N*R_dev/K, LANES) uint32, K = 32 // code_bits.
    """
    check_operands(g2d, u2d, scal)
    if block_rows % min_block_rows(code_bits):
        raise ValueError(f"block_rows={block_rows} must be a multiple of "
                         f"{min_block_rows(code_bits)} for {code_bits}-bit "
                         "codes")
    NR = g2d.shape[0]
    n_dev = scal.shape[0]
    r_dev = NR // n_dev
    blocks_per_dev = r_dev // block_rows
    K = 32 // code_bits
    rows = lambda i, j, b=blocks_per_dev: (i * b + j, 0)
    return pl.pallas_call(
        functools.partial(_pack_kernel, code_bits=code_bits),
        grid=(n_dev, blocks_per_dev),
        in_specs=[
            smem_rows(2, lambda i, j: (i, 0)),
            pl.BlockSpec((block_rows, LANES), rows),
            pl.BlockSpec((block_rows, LANES), rows),
        ],
        out_specs=pl.BlockSpec((block_rows // K, LANES), rows),
        out_shape=jax.ShapeDtypeStruct((NR // K, LANES), jnp.uint32),
        interpret=interpret,
    )(as_rows(scal), g2d, u2d)


@functools.partial(jax.jit,
                   static_argnames=("code_bits", "n_dev", "interpret",
                                    "block_rows"))
def unpack_dequant_rows_2d(p2d: jnp.ndarray, scal: jnp.ndarray,
                           code_bits: int, n_dev: int = None,
                           interpret: bool = False,
                           block_rows: int = BLOCK_ROWS) -> jnp.ndarray:
    """Inverse of quantize_pack_rows_2d: packed words -> dequantized floats.

    p2d: (N*R_dev/K, LANES) uint32; scal: (N, 2) f32 per-device
    (m, levels). Returns (N*R_dev, LANES) f32 — the materializing decoder.
    """
    check_operands(p2d, scal)
    K = 32 // code_bits
    NR = p2d.shape[0] * K
    r_dev = NR // n_dev
    blocks_per_dev = r_dev // block_rows
    rows = lambda i, j, b=blocks_per_dev: (i * b + j, 0)
    return pl.pallas_call(
        functools.partial(_unpack_kernel, code_bits=code_bits),
        grid=(n_dev, blocks_per_dev),
        in_specs=[
            smem_rows(2, lambda i, j: (i, 0)),
            pl.BlockSpec((block_rows // K, LANES), rows),
        ],
        out_specs=pl.BlockSpec((block_rows, LANES), rows),
        out_shape=jax.ShapeDtypeStruct((NR, LANES), jnp.float32),
        interpret=interpret,
    )(as_rows(scal), p2d)


@functools.partial(jax.jit,
                   static_argnames=("code_bits", "n_dev", "interpret",
                                    "block_rows", "dev_block"))
def packed_weighted_sum_2d(p2d: jnp.ndarray, scal: jnp.ndarray,
                           code_bits: int, n_dev: int = None,
                           interpret: bool = False,
                           block_rows: int = BLOCK_ROWS,
                           dev_block: int = 1) -> jnp.ndarray:
    """Fused unpack-dequantize-weighted-sum: O(d) accumulator, no (N, d).

    p2d: (N*R_dev/K, LANES) uint32 payload buffer; scal: (N, 3) per-device
    (m_i, levels_i, w_i). Returns (R_dev, LANES) = sum_i w_i * deq(p_i).
    The device axis is the innermost grid dim, so each output block
    accumulates devices 0..N-1 in order — the oracle's sequential
    association (agreement to the last ulp; only XLA's discretionary
    FMA contraction of the multiply-accumulate differs).

    ``dev_block > 1`` (requires n_dev % dev_block == 0) switches to the
    device-blocked launch: one grid step ingests dev_block whole device
    payloads (contiguous in the device-major buffer) and the kernel loop
    accumulates them in device order. N/dev_block grid steps instead of
    N * blocks_per_dev — the payload-scale configuration, where grid-step
    overhead (interpret mode copies the operand buffers every step) is
    the entire cost. Block = dev_block whole payloads, so it is
    CPU/interpret territory; TPU launches keep dev_block=1 and tile.
    """
    check_operands(p2d, scal)
    K = 32 // code_bits
    NR = p2d.shape[0] * K
    r_dev = NR // n_dev
    if dev_block > 1:
        rp_words = r_dev // K
        return pl.pallas_call(
            functools.partial(_wsum_devblock_kernel, code_bits=code_bits,
                              dev_block=dev_block, rp_words=rp_words,
                              block_rows=block_rows),
            grid=(n_dev // dev_block,),
            in_specs=[
                pl.BlockSpec((dev_block, 3), lambda mb: (mb, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((dev_block * rp_words, LANES),
                             lambda mb: (mb, 0)),
            ],
            out_specs=pl.BlockSpec((r_dev, LANES), lambda mb: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((r_dev, LANES), jnp.float32),
            interpret=interpret,
        )(scal, p2d)
    blocks_per_dev = r_dev // block_rows
    return pl.pallas_call(
        functools.partial(_wsum_kernel, code_bits=code_bits),
        grid=(blocks_per_dev, n_dev),
        in_specs=[
            smem_rows(3, lambda i, m: (m, 0)),
            pl.BlockSpec((block_rows // K, LANES),
                         lambda i, m, b=blocks_per_dev: (m * b + i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i, m: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r_dev, LANES), jnp.float32),
        interpret=interpret,
    )(as_rows(scal), p2d)
