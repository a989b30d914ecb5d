"""Pallas TPU kernels: fused digital-payload pipeline at gradient scale.

The digital uplink's hot path used to be two full passes over (N, d):
quantize-dequantize every device's gradient (materializing the f32/f64
dequantized block), then a weighted reduction. At payload scale
(d = 10^5–10^7) that block is the dominant memory term — N=256 devices at
d=10^6 is a 1 GB f32 tensor that exists only to be summed.

Three kernels replace it:

  ``quantize_pack_rows_2d``   dither → quantize → bit-pack codes into a
                              uint32 payload buffer (K = 32/code_bits
                              codes per word), one pass over (N, d).
                              This *is* the wire format: r-bit codes, not
                              dequantized floats, so the payload buffer is
                              code_bits/32 the size of the float block.
  ``packed_weighted_sum_2d``  unpack → dequantize → weighted-accumulate
                              into an O(d) accumulator. The grid walks
                              (column block, device block) with the DEVICE
                              axis innermost, and each block adds its
                              devices' rows one at a time, so every column
                              accumulates devices 0..N-1 in index order —
                              the same sequential order as the NumPy
                              oracle's ``acc += chi_m/nu_m * gq_m`` loop,
                              which keeps the fused path aligned with the
                              reference scan to the last ulp (XLA FMA
                              contraction is the only divergence). The
                              dequantized (N, d) tensor is never
                              materialized.
  ``unpack_dequant_rows_2d``  unpack → dequantize, materializing the
                              (N, d) float block — the
                              "materialize-then-sum" baseline the bench
                              compares against, and the payload decoder
                              for anything that wants per-device floats.

Layout: the kernels read the (N, d) gradients and dither as the producers
leave them, in blocks of DEV_BLOCK devices (one sublane tile) by
``block_cols`` columns; nothing is padded or reshaped first. A partial
last block of devices or of columns reads past the array's edge: the
rows it reads there are never written back, and the columns are zeroed
before packing, so no stray bits reach a neighbour's code.

Packing layout: codes are integers in [0, levels] with levels <= 2^16 - 1
(static ``code_bits`` in {4, 8, 16}). A (DEV_BLOCK, block_cols) code block
splits into K = 32/code_bits contiguous column chunks of block_cols/K
columns; chunk k fills bits [k*code_bits, (k+1)*code_bits) of the
(DEV_BLOCK, block_cols/K) word block. Every chunk is a whole number of
128-lane tiles, so packing is slice-shift-or on aligned tiles, and the
words are (N, n_col_blocks * block_cols/K). Codes survive the float
round-trip exactly (f32 represents all integers < 2^24), so pack → unpack
→ dequantize reproduces the two-step quantizer bit-for-bit. Float codes
convert to words through int32 (codes are non-negative and < 2^16, so the
int32 → uint32 step is exact).

Quantizer arithmetic is ``dithered_quant.quantize_codes``, the two-step
kernel's own; the ``levels <= 0`` / ``m == 0`` degenerate rows (devices
granted no bits) pack to code 0 and dequantize to exact 0. The per-device
scalars are an (N, width) f32 table read DEV_BLOCK rows a block, one
(DEV_BLOCK, 1) column per scalar; payload blocks may be f32 or bf16 and
are widened to f32 in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .dithered_quant import LANES, check_operands, quantize_codes

CODE_BITS_CHOICES = (4, 8, 16)

#: Devices per block: one sublane tile of f32 and uint32 rows.
DEV_BLOCK = 8


def min_block_cols(code_bits: int) -> int:
    """Smallest column block whose K packed chunks are whole 128-lane
    tiles."""
    return (32 // code_bits) * LANES


def _pack_words(q_u32, code_bits):
    """(rows, bc) uint32 codes -> (rows, bc/K) packed words."""
    K = 32 // code_bits
    cols = q_u32.shape[1] // K
    word = q_u32[:, :cols]
    for k in range(1, K):
        word = word | (q_u32[:, k * cols:(k + 1) * cols] << (k * code_bits))
    return word


def _unpack_words(word, code_bits):
    """(rows, bcw) packed words -> (rows, bcw*K) uint32 codes."""
    K = 32 // code_bits
    if K == 1:
        return word
    mask = jnp.uint32((1 << code_bits) - 1)
    return jnp.concatenate(
        [(word >> (k * code_bits)) & mask for k in range(K)], axis=1)


def _dequant(q_u32, m, levels):
    """Codes -> f32 values: -m + (2m/levels) * q, degenerate rows -> 0."""
    qf = q_u32.astype(jnp.int32).astype(jnp.float32)
    valid = (levels > 0) & (m > 0)
    safe = jnp.where(valid, 2.0 * m / jnp.where(levels > 0, levels, 1.0), 1.0)
    return jnp.where(valid, -m + safe * qf, jnp.zeros_like(qf))


def _columns(scal_ref):
    """A block's per-device scalars, one (DEV_BLOCK, 1) column each."""
    return [scal_ref[:, c:c + 1] for c in range(scal_ref.shape[1])]


def _pack_kernel(scal_ref, g_ref, u_ref, o_ref, *, code_bits, d):
    m, levels = _columns(scal_ref)
    q, _, valid = quantize_codes(g_ref[...].astype(jnp.float32), u_ref[...],
                                 m, levels)
    q = jnp.where(valid, q, jnp.zeros_like(q))

    def store(codes):
        o_ref[...] = _pack_words(codes.astype(jnp.int32).astype(jnp.uint32),
                                 code_bits)

    bc = q.shape[1]
    if d % bc == 0:
        store(q)
        return
    j = pl.program_id(1)
    last = pl.num_programs(1) - 1

    @pl.when(j < last)
    def _whole():
        store(q)

    @pl.when(j == last)
    def _partial():
        col = jax.lax.broadcasted_iota(jnp.int32, q.shape, 1)
        store(jnp.where(col < d % bc, q, jnp.zeros_like(q)))


def _unpack_kernel(scal_ref, p_ref, o_ref, *, code_bits):
    m, levels = _columns(scal_ref)
    o_ref[...] = _dequant(_unpack_words(p_ref[...], code_bits), m, levels)


def _wsum_kernel(scal_ref, p_ref, o_ref, *, code_bits, n_dev):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    m, levels, w = _columns(scal_ref)
    # unpack, dequantize and weight the whole (DEV_BLOCK, bc) block, then
    # add its rows to the accumulator one device at a time
    contrib = w * _dequant(_unpack_words(p_ref[...], code_bits), m, levels)
    acc = o_ref[...]
    for r in range(DEV_BLOCK):
        added = acc + contrib[r:r + 1]
        if n_dev % DEV_BLOCK:
            # rows past the last device read beyond the words' edge
            added = jnp.where(i * DEV_BLOCK + r < n_dev, added, acc)
        acc = added
    o_ref[...] = acc


def _grid(n_rows, n_cols, block_cols):
    return pl.cdiv(n_rows, DEV_BLOCK), pl.cdiv(n_cols, block_cols)


@functools.partial(jax.jit,
                   static_argnames=("code_bits", "interpret", "block_cols"))
def quantize_pack_rows_2d(g: jnp.ndarray, u: jnp.ndarray,
                          scal: jnp.ndarray, code_bits: int,
                          block_cols: int, interpret: bool = False
                          ) -> jnp.ndarray:
    """Fused dither-quantize-pack over N device payloads.

    g/u: (N, d), device i's gradient and dither in row i; scal: (N, 2)
    per-device (m_i, levels_i) with levels_i <= 2^code_bits-1. Returns
    (N, cdiv(d, block_cols) * block_cols / K) uint32, K = 32 // code_bits.
    """
    check_operands(g, u, scal)
    if block_cols % min_block_cols(code_bits):
        raise ValueError(f"block_cols={block_cols} must be a multiple of "
                         f"{min_block_cols(code_bits)} for {code_bits}-bit "
                         "codes")
    n_dev, d = g.shape
    K = 32 // code_bits
    grid = _grid(n_dev, d, block_cols)
    block = pl.BlockSpec((DEV_BLOCK, block_cols), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(_pack_kernel, code_bits=code_bits, d=d),
        grid=grid,
        in_specs=[pl.BlockSpec((DEV_BLOCK, 2), lambda i, j: (i, 0)),
                  block, block],
        out_specs=pl.BlockSpec((DEV_BLOCK, block_cols // K),
                               lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_dev, grid[1] * block_cols // K),
                                       jnp.uint32),
        interpret=interpret,
    )(scal, g, u)


@functools.partial(jax.jit,
                   static_argnames=("code_bits", "d", "interpret",
                                    "block_cols"))
def unpack_dequant_rows_2d(words: jnp.ndarray, scal: jnp.ndarray,
                           code_bits: int, d: int, block_cols: int,
                           interpret: bool = False) -> jnp.ndarray:
    """Inverse of quantize_pack_rows_2d: packed words -> dequantized floats.

    words: (N, W) uint32; scal: (N, 2) f32 per-device (m, levels).
    Returns (N, d) f32 — the materializing decoder.
    """
    check_operands(words, scal)
    K = 32 // code_bits
    n_dev = words.shape[0]
    return pl.pallas_call(
        functools.partial(_unpack_kernel, code_bits=code_bits),
        grid=_grid(n_dev, d, block_cols),
        in_specs=[pl.BlockSpec((DEV_BLOCK, 2), lambda i, j: (i, 0)),
                  pl.BlockSpec((DEV_BLOCK, block_cols // K),
                               lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((DEV_BLOCK, block_cols), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_dev, d), jnp.float32),
        interpret=interpret,
    )(scal, words)


@functools.partial(jax.jit,
                   static_argnames=("code_bits", "d", "interpret",
                                    "block_cols"))
def packed_weighted_sum_2d(words: jnp.ndarray, scal: jnp.ndarray,
                           code_bits: int, d: int, block_cols: int,
                           interpret: bool = False) -> jnp.ndarray:
    """Fused unpack-dequantize-weighted-sum: O(d) accumulator, no (N, d).

    words: (N, W) uint32 payload buffer; scal: (N, 3) per-device
    (m_i, levels_i, w_i). Returns (1, d) = sum_i w_i * deq(words_i).
    The device-block axis is the innermost grid dim and each block adds
    its rows in order, so each column accumulates devices 0..N-1 in
    order — the oracle's sequential association (agreement to the last
    ulp; only XLA's discretionary FMA contraction of the
    multiply-accumulate differs).
    """
    check_operands(words, scal)
    K = 32 // code_bits
    n_dev = words.shape[0]
    n_rows, n_cols = _grid(n_dev, d, block_cols)
    return pl.pallas_call(
        functools.partial(_wsum_kernel, code_bits=code_bits, n_dev=n_dev),
        grid=(n_cols, n_rows),
        in_specs=[pl.BlockSpec((DEV_BLOCK, 3), lambda j, i: (i, 0)),
                  pl.BlockSpec((DEV_BLOCK, block_cols // K),
                               lambda j, i: (i, j))],
        out_specs=pl.BlockSpec((1, block_cols), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
    )(scal, words)
