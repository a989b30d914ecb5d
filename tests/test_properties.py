"""Property-based tests (hypothesis) on system invariants."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

pytest.importorskip(
    "hypothesis",
    reason="optional dep: property tests need hypothesis; the rest of the "
           "suite must collect without it")
from hypothesis import given, settings, strategies as st
import hypothesis.extra.numpy as hnp

from repro.core import rngstream
from repro.core.sca import simplex_projection
from repro.core.quantize import quantize_np, quantization_variance_bound
from repro.core.channel import participation_probability
from repro.core.bounds import bias_sum
from repro.kernels import ops, ref

finite_floats = st.floats(-1e3, 1e3, allow_nan=False, allow_infinity=False)


@given(hnp.arrays(np.float64, st.integers(1, 40), elements=finite_floats))
@settings(max_examples=80, deadline=None)
def test_simplex_projection_valid(v):
    p = simplex_projection(v)
    assert np.all(p >= -1e-12)
    assert abs(p.sum() - 1.0) < 1e-9


@given(hnp.arrays(np.float64, st.integers(2, 30),
                  elements=st.floats(0, 1, allow_nan=False)))
@settings(max_examples=50, deadline=None)
def test_simplex_projection_idempotent_on_simplex(v):
    s = v.sum()
    if s <= 1e-9:
        return
    p0 = v / s
    p = simplex_projection(p0)
    np.testing.assert_allclose(p, p0, atol=1e-9)


@given(hnp.arrays(np.float64, st.integers(1, 40), elements=finite_floats),
       st.integers(0, 2 ** 31 - 1))
@settings(max_examples=50, deadline=None)
def test_simplex_projection_order_equivariant(v, seed):
    """Permuting the input permutes the projection: proj(Pv) == P proj(v)."""
    perm = np.random.default_rng(seed).permutation(v.shape[0])
    np.testing.assert_allclose(simplex_projection(v[perm]),
                               simplex_projection(v)[perm], atol=1e-12)


@given(hnp.arrays(np.float64, st.integers(1, 40), elements=finite_floats))
@settings(max_examples=50, deadline=None)
def test_simplex_projection_jax_matches_numpy(v):
    """The batched solver's jnp projection is the numpy rule exactly."""
    from repro.core.sca_jax import simplex_projection_jax

    with jax.enable_x64(True):
        pj = np.asarray(simplex_projection_jax(jnp.asarray(v)))
    np.testing.assert_allclose(pj, simplex_projection(v), atol=1e-12)


@given(hnp.arrays(np.float64, st.integers(1, 200),
                  elements=st.floats(-100, 100, allow_nan=False)),
       st.integers(1, 12), st.integers(0, 2**31 - 1))
@settings(max_examples=60, deadline=None)
def test_quantizer_range_and_grid(g, r, seed):
    """Quantized output stays within [-m, m] and on the grid."""
    rng = np.random.default_rng(seed)
    q = quantize_np(g, r, rng)
    m = np.max(np.abs(g))
    assert np.all(np.abs(q) <= m + 1e-9)
    s = 2 ** r - 1
    delta = 2 * m / s
    # the grid exists where its step is a normal float
    if delta >= np.finfo(np.float64).tiny:
        idx = (q + m) / delta
        np.testing.assert_allclose(idx, np.round(idx), atol=1e-6)


@given(st.integers(1, 10), st.integers(1, 16),
       st.floats(1e-6, 1e3, allow_nan=False))
@settings(max_examples=40, deadline=None)
def test_quantization_variance_bound_positive(d, r, m):
    assert quantization_variance_bound(d, r, m) >= 0


@given(hnp.arrays(np.float64, st.integers(1, 20),
                  elements=st.floats(1e-14, 1e-8)),
       st.floats(0.0, 1e-3))
@settings(max_examples=40, deadline=None)
def test_participation_probability_in_unit_interval(lam, thr):
    p = participation_probability(np.full_like(lam, thr), lam)
    assert np.all(p >= 0) and np.all(p <= 1)


@given(hnp.arrays(np.float64, st.integers(1, 30),
                  elements=st.floats(0, 1, allow_nan=False)))
@settings(max_examples=50, deadline=None)
def test_bias_sum_nonnegative_and_zero_iff_uniform(p):
    s = p.sum()
    if s <= 1e-9:
        return
    p = p / s
    b = bias_sum(p)
    assert b >= -1e-15
    n = p.shape[0]
    if np.allclose(p, 1.0 / n, atol=1e-12):
        assert b < 1e-12


@given(st.integers(0, 2 ** 31 - 1), st.integers(0, 7), st.integers(0, 300),
       st.integers(1, 5), st.integers(1, 24), st.integers(1, 24))
@settings(max_examples=15, deadline=None)
def test_batch_sampler_np_jax_bit_identical(seed, trial, t, n_devices,
                                            n_data, batch_hint):
    """The counter-based mini-batch sampler (threefry on
    seed/trial/round/device) draws bit-identical index blocks through the
    NumPy oracle view, the jitted in-scan regeneration with a traced round
    index (what the engine's lax.scan does), and the per-device fold —
    in-range and without replacement."""
    batch_size = min(batch_hint, n_data)
    block = rngstream.batch_block_np(seed, trial, t, n_devices, n_data,
                                     batch_size)
    assert block.shape == (n_devices, batch_size)
    key = rngstream.batch_base_key(seed, trial)
    jitted = jax.jit(rngstream.batch_block, static_argnums=(2, 3, 4))
    np.testing.assert_array_equal(
        np.asarray(jitted(key, jnp.asarray(t), n_devices, n_data,
                          batch_size)), block)
    for m in (0, n_devices - 1):
        np.testing.assert_array_equal(
            rngstream.batch_indices_np(seed, trial, t, m, n_data,
                                       batch_size), block[m])
    assert block.min() >= 0 and block.max() < n_data
    for row in block:
        assert len(set(row.tolist())) == batch_size   # replace=False


@given(st.integers(0, 2 ** 31 - 1), st.integers(0, 7), st.integers(0, 300))
@settings(max_examples=15, deadline=None)
def test_batch_sampler_folds_independent(seed, trial, t):
    """Adjacent (trial, round, device) key folds give distinct draws (the
    sample space 1000-choose-16 makes a collision a fold-aliasing bug), and
    the batch stream never aliases the dither stream of the same trial."""
    n_data, bs = 1000, 16
    base = rngstream.batch_indices_np(seed, trial, t, 0, n_data, bs)
    assert not np.array_equal(
        base, rngstream.batch_indices_np(seed, trial, t, 1, n_data, bs))
    assert not np.array_equal(
        base, rngstream.batch_indices_np(seed, trial, t + 1, 0, n_data, bs))
    assert not np.array_equal(
        base, rngstream.batch_indices_np(seed, trial + 1, t, 0, n_data, bs))
    assert not np.array_equal(
        rngstream.batch_base_key(seed, trial),
        rngstream.dither_base_key(seed, trial))


@given(st.integers(1, 3), st.integers(1, 300), st.integers(1, 150),
       st.integers(0, 1000))
@settings(max_examples=25, deadline=None)
def test_linear_scan_kernel_property(B, S, D, seed):
    """Kernel == sequential oracle for random stable dynamics."""
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    a = jax.random.uniform(k1, (B, S, D), minval=0.0, maxval=1.0)
    b = jax.random.normal(k2, (B, S, D)) * 0.2
    h0 = jax.random.normal(k3, (B, D))
    ha, hl = ops.linear_scan(a, b, h0, use_kernel=True)
    ra, rl = ref.linear_scan_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(ha), np.asarray(ra), atol=3e-5)
    np.testing.assert_allclose(np.asarray(hl), np.asarray(rl), atol=3e-5)
