"""Per-kernel shape/dtype sweeps: Pallas (interpret on CPU) vs ref oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

SHAPES = [(8,), (127,), (1024,), (3, 257), (2, 8, 130), (5, 1000, 7)]
DTYPES = [jnp.float32]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("levels", [1.0, 7.0, 255.0, 65535.0])
def test_dithered_quantize_matches_ref(shape, dtype, levels):
    key = jax.random.key(42)
    g = (jax.random.normal(jax.random.key(1), shape, dtype) * 3).astype(dtype)
    out_k = ops.dithered_quantize(g, levels, key, use_kernel=True)
    out_r = ops.dithered_quantize(g, levels, key, use_kernel=False)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=1e-5, rtol=1e-5)
    # quantized values must be on the quantization grid (up to fp eps)
    m = float(jnp.max(jnp.abs(g)))
    delta = 2 * m / levels
    q_idx = (np.asarray(out_k) + m) / delta
    np.testing.assert_allclose(q_idx, np.round(q_idx), atol=1e-2)


def test_dithered_quantize_zero_input():
    g = jnp.zeros((64, 64))
    out = ops.dithered_quantize(g, 255.0, jax.random.key(0), use_kernel=True)
    assert float(jnp.max(jnp.abs(out))) == 0.0


def test_dithered_quantize_unbiased():
    """E[q(g)|g] = g: average over many dither draws."""
    g = jax.random.normal(jax.random.key(5), (256,)) * 2
    acc = jnp.zeros_like(g)
    n = 400
    for i in range(n):
        acc = acc + ops.dithered_quantize(g, 15.0, jax.random.key(i),
                                          use_kernel=True)
    m = float(jnp.max(jnp.abs(g)))
    delta = 2 * m / 15.0
    np.testing.assert_allclose(np.asarray(acc / n), np.asarray(g),
                               atol=4 * delta / np.sqrt(n) + 1e-3)


@pytest.mark.parametrize("shape", SHAPES)
def test_ota_combine_matches_ref(shape):
    key = jax.random.key(3)
    g = jax.random.normal(jax.random.key(2), shape)
    a = jnp.asarray(3.7)
    ns = jnp.asarray(0.25)
    out_k = ops.ota_combine(g, a, ns, key, use_kernel=True)
    out_r = ops.ota_combine(g, a, ns, key, use_kernel=False)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=1e-6)


def test_ota_combine_zero_noise_is_scale():
    g = jax.random.normal(jax.random.key(2), (1000,))
    out = ops.ota_combine(g, jnp.asarray(2.0), jnp.asarray(0.0),
                          jax.random.key(0), use_kernel=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(g) / 2.0,
                               atol=1e-6)


@pytest.mark.parametrize("B,S,D", [(1, 16, 8), (2, 300, 200), (3, 256, 128),
                                   (2, 1024, 64), (1, 37, 129)])
def test_linear_scan_matches_ref(B, S, D):
    a = jax.random.uniform(jax.random.key(2), (B, S, D), minval=0.3,
                           maxval=0.999)
    b = jax.random.normal(jax.random.key(3), (B, S, D)) * 0.1
    h0 = jax.random.normal(jax.random.key(4), (B, D))
    ha, hl = ops.linear_scan(a, b, h0, use_kernel=True)
    ra, rl = ref.linear_scan_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(ha), np.asarray(ra), atol=2e-5)
    np.testing.assert_allclose(np.asarray(hl), np.asarray(rl), atol=2e-5)


def test_linear_scan_identity_dynamics():
    """a=1, b=0 -> h_t = h0 for all t."""
    B, S, D = 2, 512, 128
    a = jnp.ones((B, S, D))
    b = jnp.zeros((B, S, D))
    h0 = jax.random.normal(jax.random.key(0), (B, D))
    ha, hl = ops.linear_scan(a, b, h0, use_kernel=True)
    np.testing.assert_allclose(np.asarray(ha),
                               np.broadcast_to(np.asarray(h0)[:, None],
                                               (B, S, D)), atol=1e-6)
    np.testing.assert_allclose(np.asarray(hl), np.asarray(h0), atol=1e-6)


@pytest.mark.parametrize("B,S,D,n", [(1, 128, 128, 8), (2, 300, 200, 16),
                                     (2, 64, 100, 4)])
def test_selective_scan_matches_ref(B, S, D, n):
    k = jax.random.split(jax.random.key(7), 6)
    dt = jax.random.uniform(k[0], (B, S, D), minval=0.001, maxval=0.2)
    x = jax.random.normal(k[1], (B, S, D))
    bm = jax.random.normal(k[2], (B, S, n)) * 0.5
    cm = jax.random.normal(k[3], (B, S, n)) * 0.5
    aw = -jnp.exp(jax.random.normal(k[4], (D, n)) * 0.3)
    h0 = jax.random.normal(k[5], (B, D, n)) * 0.1
    yk, hk = ops.selective_scan(dt, x, bm, cm, aw, h0, use_kernel=True)
    yr, hr = ops.selective_scan(dt, x, bm, cm, aw, h0, use_kernel=False)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr), atol=3e-5)
    np.testing.assert_allclose(np.asarray(hk), np.asarray(hr), atol=3e-5)


ODD_DIMS = [1, 127, 1000, 7850, 65537]   # none divisible by BLOCK_ROWS*LANES


@pytest.mark.parametrize("d", ODD_DIMS)
def test_ota_combine_with_noise_padding(d):
    """Explicit-noise epilogue (engine hot path): pad-and-slice wrapper must
    match the jnp oracle for gradient dims not divisible by a block."""
    g = jax.random.normal(jax.random.key(d), (d,))
    z = jax.random.normal(jax.random.key(d + 1), (d,))
    out_k = ops.ota_combine_with_noise(g, jnp.asarray(2.5), z, use_kernel=True)
    out_r = ops.ota_combine_with_noise(g, jnp.asarray(2.5), z, use_kernel=False)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=1e-6)
    np.testing.assert_allclose(np.asarray(out_k), (np.asarray(g)
                               + np.asarray(z)) / 2.5, atol=1e-5)


@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_ota_combine_with_noise_dtype_and_traced_alpha(dtype):
    """The engine runs the epilogue in f32 with per-round traced
    post-scalers (Vanilla OTA); both must survive the kernel. A 64-bit
    operand is refused before it reaches the kernel (Mosaic has no 64-bit
    vector types)."""
    with jax.enable_x64(True):
        g = jnp.asarray(np.random.default_rng(0).normal(size=777), dtype)
        z = jnp.asarray(np.random.default_rng(1).normal(size=777), dtype)

        @jax.jit
        def f(alpha):
            return ops.ota_combine_with_noise(g, alpha, z, use_kernel=True)

        if dtype == "float64":
            with pytest.raises(TypeError, match="32-bit"):
                f(jnp.asarray(3.0, jnp.float32))
            return
        out = f(jnp.asarray(3.0, jnp.float32))
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out),
                               (np.asarray(g) + np.asarray(z)) / 3.0,
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("d", ODD_DIMS)
def test_dithered_quantize_with_dither_padding(d):
    """Explicit-dither quantizer vs the numpy reference on odd dims: same
    dither stream -> same payload (up to 1-ulp rounding)."""
    from repro.core.quantize import quantize_np

    class _FixedU:
        def __init__(self, u):
            self.u = u

        def uniform(self, size=None):
            return self.u

    rng = np.random.default_rng(d)
    g = rng.normal(size=d)
    u = rng.uniform(size=d)
    out_k = ops.dithered_quantize_with_dither(
        jnp.asarray(g, jnp.float32), 63.0, jnp.asarray(u, jnp.float32))
    out_r = ops.dithered_quantize_with_dither(
        jnp.asarray(g, jnp.float32), 63.0, jnp.asarray(u, jnp.float32),
        use_kernel=False)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=1e-6)
    # vs the numpy simulation quantizer (f64 arithmetic on the same f32
    # inputs): every value is on the quantization grid, and the f32 pass
    # differs only where the dither lands within f32 rounding of a
    # stochastic-rounding boundary -- one grid step, on a tiny fraction
    g32 = np.asarray(jnp.asarray(g, jnp.float32), np.float64)
    u32 = np.asarray(jnp.asarray(u, jnp.float32), np.float64)
    q_np = quantize_np(g32, 6, _FixedU(u32))
    delta = 2 * np.max(np.abs(g32)) / 63.0
    diff = np.abs(np.asarray(out_k, np.float64) - q_np)
    flips = diff > 1e-5 * delta
    assert flips.sum() <= max(1, d // 1000)
    np.testing.assert_allclose(diff[flips], delta, rtol=1e-4)


@pytest.mark.parametrize("n_dev,d", [(1, 130), (5, 127), (10, 7850),
                                     (3, 65537)])
def test_dithered_quantize_batch_matches_per_device(n_dev, d):
    """Batched rows-kernel == N independent per-device quantize calls, with
    heterogeneous per-device bit-widths (digital engine hot path)."""
    rng = np.random.default_rng(7)
    gs = jnp.asarray(rng.normal(size=(n_dev, d)) * (1 + np.arange(n_dev))[:, None],
                     jnp.float32)
    us = jnp.asarray(rng.uniform(size=(n_dev, d)), jnp.float32)
    levels = jnp.asarray([float(2 ** (1 + (i % 6)) - 1) for i in range(n_dev)],
                         jnp.float32)
    out_b = ops.dithered_quantize_batch(gs, levels, us, use_kernel=True)
    assert out_b.shape == (n_dev, d)
    for i in range(n_dev):
        out_i = ops.dithered_quantize_with_dither(gs[i], levels[i], us[i],
                                                  use_kernel=True)
        np.testing.assert_allclose(np.asarray(out_b[i]), np.asarray(out_i),
                                   atol=1e-6)


def test_mamba_kernel_flag_matches_jnp():
    """mamba_apply with the Pallas kernel == fused jnp path."""
    from repro.configs import REGISTRY
    from repro.models import make_model, make_batch, loss_fn
    cfg = REGISTRY["falcon-mamba-7b"].scaled_down()
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    batch = make_batch(cfg, 2, 40, jax.random.key(1))
    l_j, _ = loss_fn(model, params, batch, flags={"mamba_fused": True})
    l_k, _ = loss_fn(model, params, batch, flags={"mamba_kernel": True})
    np.testing.assert_allclose(float(l_j), float(l_k), rtol=1e-4)


# ------------------------------------------- fused payload pipeline

@pytest.mark.parametrize("d", ODD_DIMS)
def test_quantize_pack_roundtrip_exact(d):
    """pack -> unpack == the two-step quantize-dequantize, bit for bit:
    codes are integers < 2^24 so the uint32 round-trip through f32 is
    exact, including non-divisible dims, heterogeneous per-device
    bit-widths, and levels<=0 degenerate rows (exact zeros)."""
    rng = np.random.default_rng(d)
    n_dev = 6
    gs = jnp.asarray(rng.normal(size=(n_dev, d)), jnp.float32)
    us = jnp.asarray(rng.uniform(size=(n_dev, d)), jnp.float32)
    # device 0 granted no bits (levels=0) -> must decode to exact zeros
    levels = jnp.asarray([0.0, 1.0, 3.0, 15.0, 63.0, 255.0], jnp.float32)
    pk = ops.quantize_pack(gs, levels, us, code_bits=8)
    dec = ops.unpack_dequant(pk)
    two_step = ops.dithered_quantize_batch(gs, levels, us)
    assert dec.shape == (n_dev, d)
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(two_step))
    assert not np.any(np.asarray(dec[0]))


@pytest.mark.parametrize("code_bits", [4, 8, 16])
def test_quantize_pack_roundtrip_all_code_widths(code_bits):
    """Every packable code width (K = 32/code_bits codes per word) is a
    bit-exact inverse pair at max bit-width for that word size."""
    rng = np.random.default_rng(code_bits)
    n_dev, d = 3, 5000
    gs = jnp.asarray(rng.normal(size=(n_dev, d)), jnp.float32)
    us = jnp.asarray(rng.uniform(size=(n_dev, d)), jnp.float32)
    levels = jnp.full(n_dev, float(2 ** code_bits - 1), jnp.float32)
    pk = ops.quantize_pack(gs, levels, us, code_bits=code_bits)
    assert pk.words.dtype == jnp.uint32
    two_step = ops.dithered_quantize_batch(gs, levels, us)
    np.testing.assert_array_equal(np.asarray(ops.unpack_dequant(pk)),
                                  np.asarray(two_step))


@pytest.mark.parametrize("dtype", ["bfloat16", "float64"])
def test_quantize_pack_roundtrip_exact_dtype(dtype):
    """A bf16 payload packs bit-exactly too (codes and scalars are f32);
    a 64-bit payload is refused before it reaches the kernel."""
    with jax.enable_x64(True):
        rng = np.random.default_rng(42)
        gs = jnp.asarray(rng.normal(size=(4, 3001)), dtype)
        us = jnp.asarray(rng.uniform(size=(4, 3001)), jnp.float32)
        levels = jnp.asarray([255.0, 15.0, 0.0, 7.0], jnp.float32)
        if dtype == "float64":
            with pytest.raises(TypeError, match="32-bit"):
                ops.quantize_pack(gs, levels, us, code_bits=8)
            return
        pk = ops.quantize_pack(gs, levels, us, code_bits=8)
        dec = ops.unpack_dequant(pk)
    assert dec.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(dec),
        np.asarray(ops.dithered_quantize_batch(gs, levels, us)))


@pytest.mark.parametrize("tile", [ops.COL_BLOCK, 4096])
@pytest.mark.parametrize("n_dev,d", [(4, 1000), (8, 200_000), (5, 131_073),
                                     (100, 131_073)])
def test_quantized_weighted_sum_fused_matches_two_step(n_dev, d, tile,
                                                       monkeypatch):
    """Fused kernel == sequential jnp reference == two-step quantize +
    matvec, to accumulation-order tolerance (FMA contraction / summation
    association differ; the payload decode itself is bit-exact). Covers
    whole device blocks (n_dev=8), a single partial one (4, 5) and the
    payload cell's N=100 (12 whole blocks and a partial one). The
    65,536-column tile puts two to four column blocks in each device's
    payload at d >= 10^5; the 4096-column one, dozens."""
    monkeypatch.setattr(ops, "COL_BLOCK", tile)
    rng = np.random.default_rng(n_dev)
    gs = jnp.asarray(rng.normal(size=(n_dev, d)), jnp.float32)
    us = jnp.asarray(rng.uniform(size=(n_dev, d)), jnp.float32)
    levels = jnp.asarray([float(2 ** (1 + (i % 8)) - 1)
                          for i in range(n_dev)], jnp.float32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, size=n_dev), jnp.float32)
    fused_k = ops.quantized_weighted_sum(gs, levels, us, w, r_max=8,
                                         fused=True)
    fused_r = ops.quantized_weighted_sum(gs, levels, us, w, r_max=8,
                                         fused=True, use_kernel=False)
    two_step = ops.quantized_weighted_sum(gs, levels, us, w, fused=False)
    np.testing.assert_allclose(np.asarray(fused_k), np.asarray(fused_r),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(fused_k), np.asarray(two_step),
                               rtol=1e-5, atol=1e-6)


def _codes(pk):
    """The (N, W*K) codes packed in ``pk.words``, unpacked with NumPy in
    the kernels' layout: chunk k of each column block's block_cols/K
    columns in bits [k*code_bits, (k+1)*code_bits) of its words."""
    K = 32 // pk.code_bits
    bcw = pk.block_cols // K
    words = np.asarray(pk.words).reshape(pk.n_dev, -1, 1, bcw)
    shifts = (np.arange(K, dtype=np.uint32) * pk.code_bits).reshape(K, 1)
    codes = (words >> shifts) & np.uint32((1 << pk.code_bits) - 1)
    return codes.reshape(pk.n_dev, -1)


@pytest.mark.parametrize("n_dev,d", [(13, 5000), (13, 70_001),
                                     (8, 32_768 + 128)])
@pytest.mark.parametrize("code_bits", [4, 8, 16])
def test_pack_kernels_partial_blocks(code_bits, n_dev, d):
    """A partial last block of devices (N=13: one whole block of 8 and 5
    rows) and of columns (d not a multiple of the column block) in both
    kernels: the decode equals the two-step quantizer bit for bit, every
    code past column d is 0 (no stray bits next to a real code), and the
    fused sum agrees with the sequential reference."""
    rng = np.random.default_rng(code_bits * n_dev + d)
    gs = jnp.asarray(rng.normal(size=(n_dev, d)), jnp.float32)
    us = jnp.asarray(rng.uniform(size=(n_dev, d)), jnp.float32)
    top = 2 ** code_bits - 1
    levels = jnp.asarray([float(min(top, 2 ** (1 + i) - 1))
                          for i in range(n_dev)], jnp.float32).at[2].set(0.0)
    w = jnp.asarray(rng.uniform(0.1, 1.0, size=n_dev), jnp.float32)
    pk = ops.quantize_pack(gs, levels, us, code_bits=code_bits)
    assert d % pk.block_cols
    np.testing.assert_array_equal(
        np.asarray(ops.unpack_dequant(pk)),
        np.asarray(ops.dithered_quantize_batch(gs, levels, us)))
    assert not _codes(pk)[:, d:].any()
    fused_k = ops.packed_weighted_sum(pk, w)
    fused_r = ops.quantized_weighted_sum(gs, levels, us, w, r_max=code_bits,
                                         fused=True, use_kernel=False)
    np.testing.assert_allclose(np.asarray(fused_k), np.asarray(fused_r),
                               rtol=1e-5, atol=1e-6)


def test_quantized_weighted_sum_degenerate_device_contributes_zero():
    """A device with levels<=0 must drop out of the fused sum exactly."""
    rng = np.random.default_rng(9)
    gs = jnp.asarray(rng.normal(size=(2, 4000)), jnp.float32)
    us = jnp.asarray(rng.uniform(size=(2, 4000)), jnp.float32)
    levels = jnp.asarray([0.0, 255.0], jnp.float32)
    only_dead = ops.quantized_weighted_sum(gs, levels, us,
                                           jnp.asarray([1.0, 0.0]),
                                           r_max=8, fused=True)
    assert not np.any(np.asarray(only_dead))


def test_code_bits_for_mapping():
    """Static code-width dispatch: smallest packable width covering r_max,
    None above 16 bits (no exact f32 round-trip) or when r_max unknown."""
    assert ops.code_bits_for(None) is None
    assert ops.code_bits_for(1) == 4
    assert ops.code_bits_for(4) == 4
    assert ops.code_bits_for(5) == 8
    assert ops.code_bits_for(8) == 8
    assert ops.code_bits_for(9) == 16
    assert ops.code_bits_for(16) == 16
    assert ops.code_bits_for(17) is None


def test_ota_combine_bf16_payload_f32_accumulate():
    """bf16 gradient payload with f32 combine: output is f32 and within
    bf16 representation error of the all-f32 kernel."""
    rng = np.random.default_rng(21)
    g32 = jnp.asarray(rng.normal(size=100_003), jnp.float32)
    z = jnp.asarray(rng.normal(size=100_003), jnp.float32)
    alpha = jnp.asarray(2.5)
    out32 = ops.ota_combine_with_noise(g32, alpha, z)
    out16 = ops.ota_combine_with_noise(g32.astype(jnp.bfloat16), alpha, z,
                                       acc_dtype=jnp.float32)
    assert out16.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out16), np.asarray(out32),
                               rtol=2e-2, atol=2e-2)


def test_row_maxabs_sumsq_bf16_payload_f32_accumulate():
    """Per-device stats on a bf16 payload accumulate/return in f32 and stay
    within bf16 mantissa error of the f32 stats."""
    rng = np.random.default_rng(22)
    gs32 = jnp.asarray(rng.normal(size=(4, 70_001)), jnp.float32)
    m32, s32 = ops.row_maxabs_sumsq(gs32)
    m16, s16 = ops.row_maxabs_sumsq(gs32.astype(jnp.bfloat16))
    assert m16.dtype == jnp.float32 and s16.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(m16), np.asarray(m32), rtol=1e-2)
    np.testing.assert_allclose(np.asarray(s16), np.asarray(s32), rtol=1e-2)


@pytest.mark.parametrize("n,dtype,want", [
    (100 * 128, "float32", 128),           # below the cap: pow2 clamp
    (1000 * 128, "float32", 512),          # payload width: the constant
    (10 ** 6, "bfloat16", 512),
    (3 * 128, "float32", 8),               # one sublane tile of f32
    (3 * 128, "bfloat16", 16),             # one sublane tile of bf16
])
def test_block_rows(n, dtype, want):
    """The row tile: the smallest power of two that holds the payload's
    rows, capped at BLOCK_ROWS, never below a sublane tile."""
    assert ops._block_rows(n, dtype) == want


@pytest.mark.parametrize("d,code_bits,want", [
    (1_663_370, 8, 65_536),                # payload width: the constant
    (20_000, 8, 32_768),                   # below the cap: pow2 clamp
    (5000, 16, 8192),
    (100, 16, 256),                        # one 128-lane tile a chunk
    (100, 8, 512),
    (100, 4, 1024),
])
def test_block_cols(d, code_bits, want):
    """The pack kernels' column block: the smallest power of two that
    holds the payload's columns, capped at COL_BLOCK, never below one
    128-lane tile per packed chunk."""
    assert ops._block_cols(d, code_bits) == want
