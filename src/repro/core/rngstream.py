"""Counter-based randomness streams shared by both simulation backends.

The original oracle drew quantization dither *sequentially* from the
per-trial ``np.random.default_rng((seed, trial, 17))`` generator, which
forced the JAX engine to materialize the whole ``(trials, T, N, d)`` dither
tensor up front just to replay the stream inside ``lax.scan`` — gigabytes
for 1500-round digital horizons. Dither is therefore now *counter-based*:
the value consumed by device ``m`` in round ``t`` of trial ``trial`` is a
pure function of ``(seed, trial, t)`` computed with the threefry
``jax.random`` PRNG, identically by

  * the NumPy oracle (eagerly, via :func:`dither_block_np`, one (N, d)
    block per round), and
  * the JAX engine (inside the scan, via :func:`dither_block` on a
    scan-carried per-trial key) — O(N*d) live memory per round.

Threefry is deterministic across CPU/TPU and jit/eager, so the two
backends see bit-identical dither. Uniforms are drawn in float32: the
engine consumes them as drawn and the oracle widens them to float64
(exact). Every threshold they are compared against (participation, fault
and async tables) is rounded to float32 first (:func:`f32_table`), so the
comparisons — and the realizations — are identical in both backends.

Mini-batch sampling follows the same counter-based design: the batch
indices consumed by device ``m`` in round ``t`` of trial ``trial`` are a
pure threefry function of ``(seed, trial, t, m)`` (:func:`batch_indices` /
:func:`batch_block`), drawn without replacement. The NumPy trainer feeds
them to ``DeviceDataset.batch(..., indices=...)`` (or the stacked
``task.device_grads_at`` fast path) while the JAX engine regenerates the
(N, B) block inside its ``lax.scan`` from a scan-carried per-trial key —
bit-identical batches on both backends, and the sequential trial rng is
left untouched so the AWGN/selection replay below stays valid whether or
not mini-batching is on.

Selection randomness (UQOS' sampling permutation/keys, QML's and FedTOE's
``rng.choice``) stays on the sequential trial generator — those draws are
tiny (O(N) per round) and the engine replays them offline with
:func:`replay_rounds`, feeding the raw draws into the scan as small
``(T, S)`` inputs.

Fault injection (``core.faults``) draws one (3, N) uniform block per round
from its own counter-based stream (FAULT_TAG, :func:`fault_block` /
:func:`fault_block_np`). Like dither and batch indices — and unlike the
fast-mode-only tags below — the fault stream is counter-based in *both*
rng modes, so injected outages/erasures/stragglers are bit-identical
across rng="replay"/"fast" and across the NumPy/JAX backends.

Partial participation (``core.participation``) draws one (N,) uniform
block per round from its own counter-based stream (PARTICIPATE_TAG,
:func:`participation_block` / :func:`participation_block_np`). Like the
fault stream it is counter-based in *both* rng modes, so the sampled
cohort of every round is bit-identical across rng="replay"/"fast" and
across the NumPy/JAX backends.

Fast mode (``FLTrainer.run(..., rng="fast")``) extends the counter-based
design to *every* stream: PS AWGN (:func:`noise_block`, NOISE_TAG),
Rayleigh fading (FADING_TAG, sampled by ``channel.sample_fading_jax``)
and the per-round selection draws (SELECT_TAG, per-port ``sel_stream_jax``
samplers in the engine) become pure threefry functions of
``(seed, trial, round, stream)`` via :func:`stream_base_key`, generated
inside the scan with zero host-side per-trial precompute. Fast-mode draws
are i.i.d. from the same laws as the oracle's but form a *different*
stream — statistically equivalent (``tests/test_rng_fast.py``'s
mean-trajectory gate), not bit-equal to replay.
"""
from __future__ import annotations

from typing import Callable

import numpy as np
import jax
import jax.numpy as jnp

#: Stream tag folded into the dither key so it can never collide with other
#: derived streams of the same (seed, trial).
DITHER_TAG = 17

#: Stream tag for the mini-batch index stream (distinct from DITHER_TAG so
#: the two counter-based streams of a trial never alias).
BATCH_TAG = 29

#: Fast-mode stream tags (``rng="fast"`` only; replay mode never derives
#: these, so the oracle-parity streams above are untouched).
NOISE_TAG = 41    # PS AWGN z01 draws
FADING_TAG = 43   # Rayleigh fading (consumed via channel.sample_fading_jax)
SELECT_TAG = 47   # device-selection draws (per-port sel_stream_jax)

#: Fault-injection stream (``core.faults``): dropout / erasure / straggler
#: uniforms. Counter-based in BOTH rng modes (like dither and batch), so
#: fault realizations are bit-identical across rng="replay"/"fast" and
#: across the NumPy/JAX backends.
FAULT_TAG = 53

#: Partial-participation stream: the per-round client-sampling uniforms
#: (one (N,) block per round, ``fl.engine`` / ``fl.trainer``). Counter-based
#: in BOTH rng modes (like FAULT), so the sampled cohort of every round is
#: bit-identical across rng="replay"/"fast" and across the NumPy/JAX
#: backends.
PARTICIPATE_TAG = 59

#: Asynchronous-arrival stream (``core.async_fl``): the per-round delivery /
#: staleness uniforms of the buffered-async execution mode (one (2, N)
#: block per round). Counter-based in BOTH rng modes (like FAULT and
#: PARTICIPATE), so arrival realizations are bit-identical across
#: rng="replay"/"fast" and across the NumPy/JAX backends.
ARRIVAL_TAG = 61


def f32_table(a) -> np.ndarray:
    """A float64 threshold table rounded to float32, kept as float64.

    The f32 uniforms of the counter-based streams are compared against
    thresholds (inclusion probabilities, async rates and CDFs): the
    engine compares in f32 and the oracle in f64, so the oracle compares
    against these rounded values — the comparisons, and so the
    realizations, are then identical in both backends.
    """
    return np.asarray(np.asarray(a, np.float64).astype(np.float32),
                      np.float64)


#: Bound on the per-stream (seed, trial) -> base-key memos below.
_KEY_CACHE_MAX = 256


def _cached_base_key(cache: dict, seed: int, trial: int,
                     make: Callable[[int, int], jax.Array]) -> jax.Array:
    """Bounded-LRU memo for per-(seed, trial) base keys.

    Hits refresh recency; when full, only the least-recently-used entry is
    evicted — a sweep cycling through many (seed, trial) pairs never
    cold-restarts the keys it is actively using (the old ``.clear()``-when-
    full behavior dropped all live entries at once).
    """
    ck = (int(seed), int(trial))
    key = cache.pop(ck, None)
    if key is None:
        if len(cache) >= _KEY_CACHE_MAX:
            cache.pop(next(iter(cache)))
        key = make(seed, trial)
    cache[ck] = key          # (re)insert at the recent end
    return key


def stream_base_key(seed: int, trial: int, tag: int) -> jax.Array:
    """Per-(trial, stream) threefry base key: fold (seed, trial, tag).

    The one key-derivation rule behind every counter-based stream; round
    (and optionally device) indices are folded in later by the samplers,
    so any draw is a pure function of ``(seed, trial, tag, t[, m])``.
    """
    key = jax.random.PRNGKey(int(seed) & 0xFFFFFFFF)
    key = jax.random.fold_in(key, int(trial))
    return jax.random.fold_in(key, int(tag))


def noise_block(key: jax.Array, t, d: int) -> jnp.ndarray:
    """(d,) float32 standard-normal AWGN draws for round ``t`` (fast mode).

    ``key`` is the trial's ``stream_base_key(seed, trial, NOISE_TAG)``;
    ``t`` may be a traced scalar, so the engine folds the round index
    inside ``lax.scan`` — the replay path's (T, d) host block never
    exists. Fast mode never bit-matches the oracle's float64
    ``standard_normal`` stream anyway.
    """
    return jax.random.normal(jax.random.fold_in(key, t), (d,),
                             dtype=jnp.float32)


def dither_base_key(seed: int, trial: int) -> jax.Array:
    """Per-trial base key for the dither stream (threefry, counter-based)."""
    return stream_base_key(seed, trial, DITHER_TAG)


def dither_block(key: jax.Array, t, n: int, d: int) -> jnp.ndarray:
    """(n, d) float32 dither uniforms for round ``t`` (jit/scan-traceable).

    ``key`` is the trial's :func:`dither_base_key`; ``t`` may be a traced
    scalar, so the engine folds the round index inside ``lax.scan`` and
    never stores more than one round's block.
    """
    return jax.random.uniform(jax.random.fold_in(key, t), (n, d),
                              dtype=jnp.float32)


_DITHER_KEY_CACHE: dict = {}


def dither_block_np(seed: int, trial: int, t: int, n: int,
                    d: int) -> np.ndarray:
    """Oracle view of :func:`dither_block`: (n, d) float64 numpy array.

    The base key is memoized per (seed, trial) (bounded LRU) so the
    per-round cost in the Python training loop is one fold_in + uniform
    dispatch.
    """
    key = _cached_base_key(_DITHER_KEY_CACHE, seed, trial, dither_base_key)
    return np.asarray(dither_block(key, t, n, d), dtype=np.float64)


def fault_base_key(seed: int, trial: int) -> jax.Array:
    """Per-trial base key for the fault-injection stream (threefry)."""
    return stream_base_key(seed, trial, FAULT_TAG)


def fault_block(key: jax.Array, t, n: int) -> jnp.ndarray:
    """(3, n) float32 fault uniforms for round ``t`` (jit/scan-traceable).

    Row 0 drives dropouts, row 1 erasures, row 2 stragglers
    (``core.faults.fault_masks``). ``key`` is the trial's
    :func:`fault_base_key`; ``t`` may be a traced scalar, so the engine
    folds the round index inside ``lax.scan``. Drawn in float32 and
    compared by both backends against the f32-rounded fault
    probabilities (``core.faults.fault_masks``).
    """
    return jax.random.uniform(jax.random.fold_in(key, t), (3, n),
                              dtype=jnp.float32)


_FAULT_KEY_CACHE: dict = {}


def fault_block_np(seed: int, trial: int, t: int, n: int) -> np.ndarray:
    """Oracle view of :func:`fault_block`: (3, n) float64 numpy array.

    The base key is memoized per (seed, trial) (bounded LRU) so the
    per-round cost in the Python training loop is one fold_in + uniform
    dispatch (the dither-block pattern).
    """
    key = _cached_base_key(_FAULT_KEY_CACHE, seed, trial, fault_base_key)
    return np.asarray(fault_block(key, t, n), dtype=np.float64)


def participate_base_key(seed: int, trial: int) -> jax.Array:
    """Per-trial base key for the client-participation stream (threefry)."""
    return stream_base_key(seed, trial, PARTICIPATE_TAG)


def participation_block(key: jax.Array, t, n: int) -> jnp.ndarray:
    """(n,) float32 participation uniforms for round ``t`` (scan-traceable).

    Device ``m`` is in round ``t``'s sampled cohort iff
    ``block[m] < pi_m`` for its static inclusion probability ``pi_m``
    (``core.participation``). ``key`` is the trial's
    :func:`participate_base_key`; ``t`` may be a traced scalar, so the
    engine folds the round index inside ``lax.scan``. Drawn in float32
    and compared by both backends against the :func:`f32_table`-rounded
    inclusion probabilities.
    """
    return jax.random.uniform(jax.random.fold_in(key, t), (n,),
                              dtype=jnp.float32)


_PARTICIPATE_KEY_CACHE: dict = {}


def participation_block_np(seed: int, trial: int, t: int,
                           n: int) -> np.ndarray:
    """Oracle view of :func:`participation_block`: (n,) float64 numpy.

    The base key is memoized per (seed, trial) (bounded LRU) so the
    per-round cost in the Python training loop is one fold_in + uniform
    dispatch (the fault-block pattern).
    """
    key = _cached_base_key(_PARTICIPATE_KEY_CACHE, seed, trial,
                           participate_base_key)
    return np.asarray(participation_block(key, t, n), dtype=np.float64)


def arrival_base_key(seed: int, trial: int) -> jax.Array:
    """Per-trial base key for the async-arrival stream (threefry)."""
    return stream_base_key(seed, trial, ARRIVAL_TAG)


def arrival_block(key: jax.Array, t, n: int) -> jnp.ndarray:
    """(2, n) float32 arrival uniforms for round ``t`` (jit/scan-traceable).

    Row 0 drives the per-round delivery event (device ``m`` delivers an
    update this round iff ``block[0, m] < r_m`` for its static per-round
    completion probability), row 1 the staleness draw of the delivered
    update (compared against the device's precomputed truncated-geometric
    CDF thresholds, ``core.async_fl``). ``key`` is the trial's
    :func:`arrival_base_key`; ``t`` may be a traced scalar, so the engine
    folds the round index inside ``lax.scan``. Drawn in float32 and
    compared by both backends against the :func:`f32_table`-rounded
    rate/CDF tables.
    """
    return jax.random.uniform(jax.random.fold_in(key, t), (2, n),
                              dtype=jnp.float32)


_ARRIVAL_KEY_CACHE: dict = {}


def arrival_block_np(seed: int, trial: int, t: int, n: int) -> np.ndarray:
    """Oracle view of :func:`arrival_block`: (2, n) float64 numpy array.

    The base key is memoized per (seed, trial) (bounded LRU) so the
    per-round cost in the Python training loop is one fold_in + uniform
    dispatch (the fault-block pattern).
    """
    key = _cached_base_key(_ARRIVAL_KEY_CACHE, seed, trial, arrival_base_key)
    return np.asarray(arrival_block(key, t, n), dtype=np.float64)


def batch_base_key(seed: int, trial: int) -> jax.Array:
    """Per-trial base key for the mini-batch index stream (threefry)."""
    return stream_base_key(seed, trial, BATCH_TAG)


def batch_indices(key: jax.Array, t, m, n_data: int,
                  batch_size: int) -> jnp.ndarray:
    """(batch_size,) int32 without-replacement sample of range(n_data) for
    device ``m`` in round ``t`` (jit/scan-traceable).

    ``key`` is the trial's :func:`batch_base_key`; ``t`` and ``m`` may be
    traced scalars. The fold order (round, then device) matches
    :func:`batch_block`, so the block's row ``m`` equals this draw exactly.
    """
    km = jax.random.fold_in(jax.random.fold_in(key, t), m)
    return jax.random.choice(km, n_data, (batch_size,),
                             replace=False).astype(jnp.int32)


def batch_block(key: jax.Array, t, n_devices: int, n_data: int,
                batch_size: int) -> jnp.ndarray:
    """(n_devices, batch_size) int32 batch indices for round ``t``.

    Row ``m`` is :func:`batch_indices` for device ``m`` — the engine calls
    this inside ``lax.scan`` on a scan-carried key, so only one round's
    block is ever live (O(N*B) memory, mirroring the dither-block design).
    """
    kt = jax.random.fold_in(key, t)
    keys = jax.vmap(lambda m: jax.random.fold_in(kt, m))(
        jnp.arange(n_devices))
    return jax.vmap(
        lambda k: jax.random.choice(k, n_data, (batch_size,), replace=False)
    )(keys).astype(jnp.int32)


def batch_block_ragged(key: jax.Array, t, sizes: tuple,
                       batch_size: int) -> jnp.ndarray:
    """(len(sizes), batch_size) int32 batch indices for round ``t`` when
    device datasets have *unequal* sizes.

    Row ``m`` samples ``range(sizes[m])`` without replacement with the key
    ``fold_in(fold_in(key, t), m)`` — bit-identical to the per-device
    :func:`batch_indices` draw the NumPy oracle makes with each device's
    own ``n_data``, so the engine's padded-stack gather sees the exact
    oracle batches. ``sizes`` must be static (trace-time Python ints);
    every row needs ``batch_size <= sizes[m]``, and indices never reach
    the padding rows (``idx < sizes[m] <= n_max``).
    """
    kt = jax.random.fold_in(key, t)
    rows = [jax.random.choice(jax.random.fold_in(kt, m), int(n_m),
                              (batch_size,), replace=False)
            for m, n_m in enumerate(sizes)]
    return jnp.stack(rows).astype(jnp.int32)


def batch_block_mixed(key: jax.Array, t, sizes: tuple,
                      batch_size: int) -> jnp.ndarray:
    """(len(sizes), batch_size) int32 batch indices for round ``t`` in the
    *mixed* full/mini-batch regime (unequal sizes, batch_size >= some
    ``sizes[m]``).

    Mini-batch rows (``sizes[m] > batch_size``) are the exact
    :func:`batch_block_ragged` draw — ``fold_in(fold_in(key, t), m)``,
    bit-identical to the oracle's per-device :func:`batch_indices_np`.
    Full-batch rows (``sizes[m] <= batch_size``) consume *no* draw,
    mirroring the oracle's ``indices=None`` full-dataset path: the row is
    the static gather ``min(arange(batch_size), sizes[m]-1)`` — columns
    past ``sizes[m]`` duplicate the last sample and carry weight 0 in the
    engine's weighted-gradient reduction, so they never contribute.
    ``sizes`` must be static (trace-time Python ints).
    """
    kt = jax.random.fold_in(key, t)
    rows = []
    for m, n_m in enumerate(sizes):
        n_m = int(n_m)
        if n_m > batch_size:
            rows.append(jax.random.choice(jax.random.fold_in(kt, m), n_m,
                                          (batch_size,), replace=False))
        else:
            rows.append(jnp.minimum(jnp.arange(batch_size), n_m - 1))
    return jnp.stack(rows).astype(jnp.int32)


_BATCH_KEY_CACHE: dict = {}


def _batch_key_np(seed: int, trial: int) -> jax.Array:
    return _cached_base_key(_BATCH_KEY_CACHE, seed, trial, batch_base_key)


def batch_indices_np(seed: int, trial: int, t: int, m: int, n_data: int,
                     batch_size: int) -> np.ndarray:
    """Oracle view of :func:`batch_indices` (one device): (B,) int numpy.

    Used by the NumPy trainer when device datasets have unequal sizes and
    the stacked block path can't apply; keyed on this device's own
    ``n_data`` so the draw is still a pure counter function.
    """
    key = _batch_key_np(seed, trial)
    return np.asarray(batch_indices(key, t, m, n_data, batch_size))


def batch_block_np(seed: int, trial: int, t: int, n_devices: int,
                   n_data: int, batch_size: int) -> np.ndarray:
    """Oracle view of :func:`batch_block`: (N, B) int numpy array.

    The base key is memoized per (seed, trial) so the per-round cost in the
    Python training loop is one fold_in + vmapped choice dispatch.
    """
    key = _batch_key_np(seed, trial)
    return np.asarray(batch_block(key, t, n_devices, n_data, batch_size))


def trial_rng(seed: int, trial: int) -> np.random.Generator:
    """The sequential per-trial generator used by the NumPy trainer."""
    return np.random.default_rng((seed, trial, 17))


def replay_rounds(seed: int, trial: int, rounds: int,
                  draw_fn: Callable[[np.random.Generator], np.ndarray]
                  ) -> np.ndarray:
    """Replay ``rounds`` per-round draws of the oracle's trial generator.

    ``draw_fn(rng)`` must consume *exactly* what the scheme's
    ``Aggregator.round`` consumes from the trial rng in one round (its
    selection draws), in the same order, and return them as a flat float64
    row. Returns the (rounds, S) stack the engine feeds into its scan.
    """
    rng = trial_rng(seed, trial)
    rows = [np.asarray(draw_fn(rng), dtype=np.float64).ravel()
            for _ in range(rounds)]
    if not rows:
        return np.zeros((0, 1))
    return np.stack(rows)
