"""jit'd wrappers around the Pallas kernels (padding, reshapes, fallbacks).

``use_kernel=False`` routes to the pure-jnp oracle (kernels/ref.py); on CPU
the kernels execute in Pallas interpret mode, on TPU they compile to
Mosaic. All wrappers accept arbitrary-shaped operands.

Dtype contract: kernel operands are f32, or bf16 for a gradient payload;
scalars, accumulation and results are f32 (:func:`_wide`). A 64-bit
operand never reaches a kernel: the ``*_2d`` entry points refuse it.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from . import ref
from .dithered_quant import (dithered_quantize_2d, dithered_quantize_rows_2d,
                             LANES)
from .ota_combine import ota_combine_2d
from .linear_scan import linear_scan_fsl, CHUNK
from .row_reduce import row_maxabs_sumsq_2d
from .payload import (quantize_pack_rows_2d, unpack_dequant_rows_2d,
                      packed_weighted_sum_2d, CODE_BITS_CHOICES,
                      min_block_rows)

# Below this payload dimension the fused pack path is not worth the extra
# kernel: the two-step quantize + matvec fits one or two tiles anyway and
# stays the bit-compared parity path for the paper-scale figures.
FUSED_MIN_DIM = 1 << 17


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _wide(dtype):
    """Accumulation dtype for a payload dtype: f32 for f32/bf16 payloads
    (the ``use_kernel=False`` oracle keeps an f64 input in f64)."""
    return jnp.promote_types(dtype, jnp.float32)


def _sublane_rows(dtype) -> int:
    """Rows in one (sublane, 128) tile: 8 for 32-bit, 16 for bf16."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


#: Row tile of every kernel launch on a payload of BLOCK_ROWS rows or more.
#: One constant until a chip measurement picks tiles per kernel: every
#: kernel compiles for a v5e at it, in f32 and bf16
#: (``tests/test_chip_compile.py``). The v5e VMEM ceilings are far above
#: it: 4096 rows for ``ota_combine``, 2048 for the f32 dithered quantizers.
BLOCK_ROWS = 512


def _block_rows(n: int, dtype, min_rows: int = 8) -> int:
    """Row tile for an n-element payload: the smallest power of two that
    holds all its rows, capped at BLOCK_ROWS. Never below one sublane tile
    of ``dtype`` or ``min_rows``."""
    rows = -(-n // LANES)
    br = 8
    while br < min(rows, BLOCK_ROWS):
        br *= 2
    return max(br, min_rows, _sublane_rows(dtype))


def _to_blocks(x: jnp.ndarray, block_rows: int):
    """Flatten + zero-pad to (R, LANES) with R % block_rows == 0."""
    n = x.size
    per = block_rows * LANES
    n_pad = (-n) % per
    flat = jnp.pad(x.reshape(-1), (0, n_pad))
    return flat.reshape(-1, LANES), n


def _from_blocks(y2d: jnp.ndarray, n: int, shape, dtype):
    return y2d.reshape(-1)[:n].reshape(shape).astype(dtype)


def dithered_quantize(g: jnp.ndarray, levels: jnp.ndarray, key: jax.Array,
                      *, use_kernel: bool = True) -> jnp.ndarray:
    """Dithered stochastic uniform quantize-dequantize of a full tensor."""
    dither = jax.random.uniform(key, g.shape, dtype=jnp.float32)
    return dithered_quantize_with_dither(g, levels, dither,
                                         use_kernel=use_kernel)


def dithered_quantize_with_dither(g: jnp.ndarray, levels: jnp.ndarray,
                                  dither: jnp.ndarray,
                                  *, use_kernel: bool = True) -> jnp.ndarray:
    """Quantize-dequantize with an explicit dither operand (g's shape).

    Used by the FL engine, which replays the NumPy trainer's dither stream
    for bit-parity instead of drawing from a jax PRNG key. Returns the
    dequantized tensor in the accumulation dtype (f32 for f32/bf16 g).
    """
    wide = _wide(g.dtype)
    m = jnp.max(jnp.abs(g)).astype(wide)
    levels = jnp.asarray(levels, wide)
    if not use_kernel:
        return ref.dithered_quantize_ref(g.astype(wide), m, levels,
                                         dither.astype(wide))
    br = _block_rows(g.size, g.dtype)
    g2d, n = _to_blocks(g, br)
    u2d, _ = _to_blocks(dither.astype(jnp.float32), br)
    out = dithered_quantize_2d(g2d, u2d, m, levels, interpret=_on_cpu(),
                               block_rows=br)
    return _from_blocks(out, n, g.shape, wide)


def dithered_quantize_batch(gs: jnp.ndarray, levels: jnp.ndarray,
                            dither: jnp.ndarray,
                            *, use_kernel: bool = True) -> jnp.ndarray:
    """Quantize N independent tensors (rows of ``gs``) in one fused launch.

    gs/dither: (N, d); levels: (N,) per-device 2^{r_m} - 1. Each row is
    normalized by its own ||g_m||_inf — the digital-FL uplink where every
    device compresses with its offline-designed bit-width (Sec. II-B).
    Returns the dequantized block in the accumulation dtype.
    """
    wide = _wide(gs.dtype)
    m = jnp.max(jnp.abs(gs), axis=1).astype(wide)
    levels = jnp.asarray(levels, wide)
    if not use_kernel:
        return jax.vmap(ref.dithered_quantize_ref)(
            gs.astype(wide), m, levels, dither.astype(wide))
    n_dev, d = gs.shape
    br = _block_rows(d, gs.dtype)
    per = br * LANES
    d_pad = (-d) % per
    pad = lambda x: jnp.pad(x, ((0, 0), (0, d_pad))).reshape(-1, LANES)
    scal = jnp.stack([m, levels], axis=1)
    dither = dither.astype(jnp.float32)
    out = dithered_quantize_rows_2d(pad(gs), pad(dither), scal,
                                    interpret=_on_cpu(), block_rows=br)
    return out.reshape(n_dev, d + d_pad)[:, :d]


def code_bits_for(r_max) -> int | None:
    """Smallest packable code width covering r_max-bit quantizers.

    Codes are integers in [0, 2^r - 1]; supported packed widths are
    CODE_BITS_CHOICES = (4, 8, 16). 16 is the ceiling on purpose: wider
    codes would not survive the f32 round-trip exactly (f32 represents
    integers only up to 2^24) and r > 16 bits/entry has no compression
    story anyway. Returns None when no fused path applies.
    """
    if r_max is None:
        return None
    r = int(r_max)
    for cb in CODE_BITS_CHOICES:
        if r <= cb:
            return cb
    return None


@dataclasses.dataclass
class PackedGrads:
    """Bit-packed device payload buffer (the digital uplink wire format).

    words holds each device's quantizer codes at ``code_bits`` per entry,
    K = 32/code_bits codes per uint32 — code_bits/32 the bytes of the
    float block it replaces. scal: (N, 2) f32 per-device (||g||_inf,
    levels).
    """
    words: jnp.ndarray        # (N * R_dev / K, LANES) uint32
    scal: jnp.ndarray         # (N, 2)
    code_bits: int
    n_dev: int
    d: int
    block_rows: int


def quantize_pack(gs: jnp.ndarray, levels: jnp.ndarray, dither: jnp.ndarray,
                  *, code_bits: int) -> PackedGrads:
    """Fused dither -> quantize -> bit-pack of N device gradients.

    gs/dither: (N, d); levels: (N,) per-device 2^{r_m} - 1 with
    r_m <= code_bits. One Pallas pass per device block; the dequantized
    float tensor is never formed.
    """
    n_dev, d = gs.shape
    m = jnp.max(jnp.abs(gs), axis=1).astype(jnp.float32)
    levels = jnp.asarray(levels, jnp.float32)
    dither = dither.astype(jnp.float32)
    br = _block_rows(d, gs.dtype, min_rows=min_block_rows(code_bits))
    per = br * LANES
    d_pad = (-d) % per
    pad = lambda x: jnp.pad(x, ((0, 0), (0, d_pad))).reshape(-1, LANES)
    scal = jnp.stack([m, levels], axis=1)
    words = quantize_pack_rows_2d(pad(gs), pad(dither), scal,
                                  code_bits=code_bits,
                                  interpret=_on_cpu(), block_rows=br)
    return PackedGrads(words, scal, code_bits, n_dev, d, br)


def unpack_dequant(pk: PackedGrads) -> jnp.ndarray:
    """Decode a packed payload buffer back to (N, d) dequantized floats.

    The materializing decoder — bit-exact inverse of the two-step
    ``dithered_quantize_batch`` output, and the O(N*d) baseline the fused
    ``packed_weighted_sum`` is benchmarked against.
    """
    out = unpack_dequant_rows_2d(pk.words, pk.scal, code_bits=pk.code_bits,
                                 n_dev=pk.n_dev, interpret=_on_cpu(),
                                 block_rows=pk.block_rows)
    return out.reshape(pk.n_dev, -1)[:, :pk.d]


def _dev_block(n_dev: int) -> int:
    """Devices per grid step for the fused accumulate. On CPU/interpret
    the per-grid-step overhead dominates (every step copies the operand
    buffers), so group as many whole payloads per step as divide N; on
    TPU a multi-payload block would blow VMEM, so keep the tiled launch
    (dev_block=1, the launch ``tests/test_chip_compile.py`` compiles)."""
    if not _on_cpu():
        return 1
    for db in (16, 8, 4, 2):
        if n_dev % db == 0:
            return db
    return 1


def packed_weighted_sum(pk: PackedGrads, weights: jnp.ndarray) -> jnp.ndarray:
    """sum_i w_i * dequant(payload_i) with an O(d) accumulator.

    Unpacks, dequantizes and accumulates per block with the device axis
    innermost — device-index order, the NumPy oracle's (and
    ``ref.quantized_weighted_sum_ref``'s) sequential association, agreeing
    to the last ulp (FMA contraction) — without materializing the (N, d)
    dequantized tensor.
    """
    w = jnp.asarray(weights, jnp.float32).reshape(-1, 1)
    scal3 = jnp.concatenate([pk.scal, w], axis=1)
    out = packed_weighted_sum_2d(pk.words, scal3, code_bits=pk.code_bits,
                                 n_dev=pk.n_dev, interpret=_on_cpu(),
                                 block_rows=pk.block_rows,
                                 dev_block=_dev_block(pk.n_dev))
    return out.reshape(-1)[:pk.d]


def quantized_weighted_sum(gs: jnp.ndarray, levels: jnp.ndarray,
                           dither: jnp.ndarray, weights: jnp.ndarray,
                           *, r_max=None, use_kernel: bool = True,
                           fused="auto") -> jnp.ndarray:
    """The digital aggregation hot path: sum_i w_i * quantize(g_i).

    Dispatches between the legacy two-step path (quantize-dequantize the
    (N, d) block, then a weighted matvec — the bit-compared parity path
    for paper-scale payloads) and the fused pack path (quantize straight
    into a uint32 code buffer, then unpack-dequant-accumulate with an
    O(d) accumulator — the payload-scale path).

    ``r_max``: static upper bound on any device's bit-width this round
    (each scheme knows its own); required for the fused path since the
    packed code width is static. ``fused="auto"`` fuses only when a
    packable r_max is known and d >= FUSED_MIN_DIM; pass True/False to
    force. ``use_kernel=False`` with fused=True runs the sequential-order
    jnp reference (same accumulation order as the fused kernel).
    """
    cb = code_bits_for(r_max)
    d = gs.shape[1]
    if fused == "auto":
        fused = use_kernel and cb is not None and d >= FUSED_MIN_DIM
    if not fused:
        gq = dithered_quantize_batch(gs, levels, dither,
                                     use_kernel=use_kernel)
        return jnp.asarray(weights, gq.dtype) @ gq
    if not use_kernel:
        wide = _wide(gs.dtype)
        m = jnp.max(jnp.abs(gs), axis=1).astype(wide)
        return ref.quantized_weighted_sum_ref(
            gs.astype(wide), m, jnp.asarray(levels, wide),
            dither.astype(wide), jnp.asarray(weights, wide))
    if cb is None:
        raise ValueError(
            f"fused quantized_weighted_sum needs a static r_max <= "
            f"{max(CODE_BITS_CHOICES)} (got r_max={r_max})")
    pk = quantize_pack(gs, levels, dither, code_bits=cb)
    return packed_weighted_sum(pk, weights)


def row_maxabs_sumsq(gs: jnp.ndarray, *, use_kernel: bool = True):
    """Per-device gradient statistics in one fused pass.

    gs: (N, d) f32 or bf16. Returns (maxabs (N,), sumsq (N,)) in the
    accumulation dtype: ``||g_m||_inf`` (the quantizer scale /
    quantization-MSE ingredient d*maxabs^2/(2^r-1)^2) and ``sum g_m^2``
    (norm-based scheduling scores), computed by the Pallas row-reduction
    kernel (interpret on CPU, Mosaic on TPU). A bf16 payload accumulates
    in f32: a bf16 sum of squares saturates after a few hundred terms.
    """
    if not use_kernel:
        ga = gs.astype(_wide(gs.dtype))
        return jnp.max(jnp.abs(ga), axis=1), jnp.sum(ga * ga, axis=1)
    n_dev, d = gs.shape
    br = _block_rows(d, gs.dtype)
    per = br * LANES
    d_pad = (-d) % per
    g2d = jnp.pad(gs, ((0, 0), (0, d_pad))).reshape(-1, LANES)
    out = row_maxabs_sumsq_2d(g2d, n_dev=n_dev, interpret=_on_cpu(),
                              block_rows=br)
    return out[:, 0], out[:, 1]


def ota_combine_with_noise(g: jnp.ndarray, alpha: jnp.ndarray,
                           noise: jnp.ndarray,
                           *, use_kernel: bool = True,
                           acc_dtype=None) -> jnp.ndarray:
    """ghat = (g + noise)/alpha with an explicit AWGN operand (eq. (6)).

    ``alpha`` may be a traced per-round scalar (e.g. Vanilla OTA's n*gamma_t).
    The kernel consumes pre-scaled noise, so this computes
    g*inv_alpha + noise*inv_alpha (1-ulp from the reference (g+z)/alpha).
    ``acc_dtype`` sets a wider accumulate/output dtype than the payload
    (bf16 gradient payload, f32 combine); default g.dtype.
    """
    out_dt = g.dtype if acc_dtype is None else jnp.dtype(acc_dtype)
    inv_alpha = (1.0 / jnp.asarray(alpha)).astype(out_dt)
    z = noise.astype(out_dt) * inv_alpha
    if not use_kernel:
        return ref.ota_combine_ref(g.astype(out_dt), inv_alpha, z)
    br = _block_rows(g.size, g.dtype)
    g2d, n = _to_blocks(g, br)
    z2d, _ = _to_blocks(z, br)
    out = ota_combine_2d(g2d, z2d, inv_alpha, interpret=_on_cpu(),
                         block_rows=br, acc_dtype=acc_dtype)
    return _from_blocks(out, n, g.shape, out_dt)


def ota_combine(g: jnp.ndarray, alpha: jnp.ndarray, noise_scale: jnp.ndarray,
                key: jax.Array, *, use_kernel: bool = True) -> jnp.ndarray:
    """ghat = g/alpha + noise_scale * N(0,1) (noise_scale already /alpha)."""
    inv_alpha = (1.0 / alpha).astype(g.dtype)
    z = (noise_scale.astype(jnp.float32)
         * jax.random.normal(key, g.shape, jnp.float32)).astype(g.dtype)
    if not use_kernel:
        return ref.ota_combine_ref(g, inv_alpha, z)
    br = _block_rows(g.size, g.dtype)
    g2d, n = _to_blocks(g, br)
    z2d, _ = _to_blocks(z, br)
    out = ota_combine_2d(g2d, z2d, inv_alpha, interpret=_on_cpu(),
                         block_rows=br)
    return _from_blocks(out, n, g.shape, g.dtype)


def selective_scan(dt, x, bm, cm, a_w, h0, *, use_kernel: bool = True):
    """Fused Mamba-1 selective scan. dt/x: (B,S,D); bm/cm: (B,S,n);
    a_w: (D,n); h0: (B,D,n). Returns (y (B,S,D), h_last (B,D,n))."""
    if not use_kernel:
        return ref.selective_scan_ref(dt, x, bm, cm, a_w, h0)
    from .selective_scan import selective_scan_bfsn, CHUNK as SCHUNK
    B, S, D = dt.shape
    n = bm.shape[-1]
    s_pad = (-S) % SCHUNK
    d_pad = (-D) % LANES
    dt_p = jnp.pad(dt, ((0, 0), (0, s_pad), (0, d_pad)))
    x_p = jnp.pad(x, ((0, 0), (0, s_pad), (0, d_pad)))
    bm_p = jnp.pad(bm, ((0, 0), (0, s_pad), (0, 0)))
    cm_p = jnp.pad(cm, ((0, 0), (0, s_pad), (0, 0)))
    a_p = jnp.pad(a_w, ((0, d_pad), (0, 0)))
    h0_p = jnp.pad(h0, ((0, 0), (0, d_pad), (0, 0)))
    Sp, Dp = S + s_pad, D + d_pad
    F = Dp // LANES
    to_bfs = lambda t: t.reshape(B, Sp, F, LANES).transpose(0, 2, 1, 3)
    a_f = a_p.reshape(F, LANES, n)
    h0_f = h0_p.reshape(B, F, LANES, n).transpose(0, 1, 3, 2)
    y, h_last = selective_scan_bfsn(to_bfs(dt_p), to_bfs(x_p), bm_p, cm_p,
                                    a_f, h0_f, interpret=_on_cpu())
    y = y.transpose(0, 2, 1, 3).reshape(B, Sp, Dp)[:, :S, :D]
    h_last = h_last.transpose(0, 1, 3, 2).reshape(B, Dp, n)[:, :D]
    return y, h_last


def linear_scan(a: jnp.ndarray, b: jnp.ndarray, h0: jnp.ndarray,
                *, use_kernel: bool = True):
    """h_t = a_t h_{t-1} + b_t over axis 1. a,b: (B,S,D); h0: (B,D).

    Returns (h_all, h_last). Kernel path pads S to a CHUNK multiple and
    D to a LANES multiple (pad a=1, b=0 so padding is inert).
    """
    if not use_kernel:
        return ref.linear_scan_ref(a, b, h0)
    B, S, D = a.shape
    s_pad = (-S) % CHUNK
    d_pad = (-D) % LANES
    a_p = jnp.pad(a, ((0, 0), (0, s_pad), (0, d_pad)), constant_values=1.0)
    b_p = jnp.pad(b, ((0, 0), (0, s_pad), (0, d_pad)))
    h0_p = jnp.pad(h0, ((0, 0), (0, d_pad)))
    Sp, Dp = S + s_pad, D + d_pad
    # (B, Sp, Dp) -> (B*Dp/LANES, Sp, LANES): feature-major blocks
    a_f = a_p.transpose(0, 2, 1).reshape(B * Dp // LANES, LANES, Sp)
    a_f = a_f.transpose(0, 2, 1)
    b_f = b_p.transpose(0, 2, 1).reshape(B * Dp // LANES, LANES, Sp)
    b_f = b_f.transpose(0, 2, 1)
    h0_f = h0_p.reshape(B * Dp // LANES, 1, LANES)
    h_all, h_last = linear_scan_fsl(a_f, b_f, h0_f, interpret=_on_cpu())
    h_all = h_all.transpose(0, 2, 1).reshape(B, Dp, Sp).transpose(0, 2, 1)
    return h_all[:, :S, :D], h_last.reshape(B, Dp)[:, :D]
