"""JAX-native vectorized FL simulation engine.

The NumPy trainer (``fl/trainer.py`` + ``core/baselines.py``) runs the
paper's Monte-Carlo protocol with Python-level ``for trial / for t`` loops —
the reference oracle, but slow. This engine runs the same (trials, rounds)
recursion of eq. (2)/(13) as ``vmap(lax.scan)`` over a *functional*
aggregator protocol, with the PS epilogue (post-scale + AWGN, eq. (6))
dispatched through the fused Pallas kernel ``kernels/ota_combine.py``, the
digital payload compressor through ``kernels/dithered_quant.py``, and the
per-device gradient scoring (norm/quantization-MSE selection) through
``kernels/row_reduce.py`` (interpret mode on CPU, Mosaic on TPU). Every
scheme in ``core.baselines`` has a port registered in ``_PORT_FACTORIES``,
so ``backend="jax"`` covers the paper's full Sec. V comparison suite.

Two RNG execution modes (``run(..., rng=...)``):

  * ``rng="replay"`` (default) — bit-reproduces the NumPy oracle's random
    streams (contract below), at the cost of O(T*(d+S)) host-side NumPy
    precompute per trial (AWGN blocks, fading stacks, selection replays)
    before the jitted scan starts;
  * ``rng="fast"`` — every stream (AWGN, fading, dither, selection, batch
    indices) is generated counter-based *inside* the scan, threefry-keyed
    on ``(seed, trial, round, stream)`` (``core.rngstream`` tags), with
    zero host-side per-trial precompute and O(N*d) live memory. Same
    distributions, different stream: statistically equivalent to replay
    (mean trajectories match within MC tolerance,
    ``tests/test_rng_fast.py``), not bit-equal — the mode for
    population-scale N / trial counts where the replay tax dominates.

RNG-replay contract — the engine reproduces the NumPy trainer's random
streams, so the two backends agree to f32 round-off over hundreds of
rounds (``tests/test_engine_parity.py`` documents each tolerance):

  * fading: ``channel.sample_fading_batch`` reproduces
    ``FadingProcess(dep, seed*1000 + trial).sample(t)`` bit-for-bit on the
    host; the scan consumes it rounded to complex64;
  * PS AWGN: every OTA aggregator draws exactly one ``normal(d)`` per round
    from the sequential trial rng ``default_rng((seed, trial, 17))``, so one
    ``standard_normal((T, d))`` block per trial replays the stream;
  * quantization dither is *counter-based* (``core.rngstream``): the (N, d)
    uniform block of round ``t`` is a pure threefry function of
    ``(seed, trial, t)``, generated eagerly by the oracle and regenerated
    inside the scan from a scan-carried per-trial key — O(N*d) live memory
    per round, no materialized (trials, T, N, d) tensor, which is what makes
    1500-round digital horizons feasible;
  * device-selection draws (UQOS' sampling permutation/keys, QML's and
    FedTOE's ``rng.choice``) stay on the sequential trial rng; each port's
    ``sel_stream_np`` replays them offline into a small (T, S) array that
    rides into the scan alongside the fading;
  * mini-batch indices are counter-based like the dither
    (``rngstream.batch_block``, threefry keyed on seed/trial/round/device):
    the engine regenerates each round's (N, B) index block from a
    scan-carried key and gathers the batches through the task's
    ``device_grads_at_fn`` — the exact compiled program the NumPy trainer
    calls on the same indices, so stochastic gradients are bit-identical.

Fault injection (``core.faults.FaultSpec``) runs in-scan too: one (3, N)
counter-based uniform block per round (FAULT_TAG — bit-identical across
both rng modes and both backends) drives dropout/erasure/straggler masks,
deep fades evaluate through ``digital.outage_mask``, and the
``on_missing`` degradation policy (reweight/zero/stale) transforms the
gradient payloads *before* the scheme's ``round_fn`` so every registered
port inherits it; "stale" carries the last received (N, d) gradients in
the scan carry. With faults disabled the scan traces the exact pre-fault
program — disabled-fault runs are bit-identical to a fault-free build.

Partial participation (``core.participation``) runs in-scan the same way:
one (N,) counter-based uniform block per round (PARTICIPATE_TAG —
bit-identical across both rng modes and both backends) draws the Bernoulli
cohort ``chi_m = u_m < pi_m``; excluded payloads zero out and included
ones carry the uniform inverse-propensity scale N/S, upstream of the
fault layer and every scheme's combiner. ``clients_per_round=None``
traces the exact pre-participation program (bit-identical runs).

Buffered-async mode (``core.async_fl``, ``mode="async"``) runs in-scan as
well: the scan carries a (K, N, d) last-K gradient buffer, one (2, N)
counter-based uniform block per round (ARRIVAL_TAG — bit-identical across
both rng modes and both backends) draws each device's delivery event and
staleness against precomputed f32-rounded rate/CDF tables, and the delivered
payload ``delta^S * v_m * (N/sum(cv)) * g_m(w_{t-S})`` replaces the fresh
gradient upstream of the fault layer and every scheme's combiner
(missing devices zero-fill or replay their last delivered payload through
``async_fl.stale_replace`` — the same code path as
``fault.on_missing="stale"``). ``mode="sync"`` (default) traces the exact
pre-async program (bit-identical runs).

Time budgets run in-scan: cumulative wall-clock rides in the scan carry,
every round is masked by ``t_wall < budget`` (``jnp.where``), and each eval
segment reports the last *live* model state — replicating the trainer's
freeze-at-last-written-eval semantics exactly, including the wall-clock
pinned at the budget-exhaustion time (``tests/test_trainer_budget.py``).

Dtype contract: everything on the device is float32 — the model state,
the (N, d) gradients, dither and noise, the async and stale buffers and
every Pallas kernel operand (bf16 payloads are rounded to bf16 and
accumulated in f32). float64 lives only on the host: the NumPy oracle and
the replay/participation/fault/async tables, which enter the scan rounded
to f32. The counter-based uniforms are drawn in f32 and compared against
those f32-rounded tables by both backends, so their realizations stay
bit-identical; trajectories agree to f32 round-off. Caveat: dither replay
assumes participating gradients are nonzero (``quantize_np`` skips its
quantization on an exactly-zero gradient, which is measure-zero for the
paper's tasks).

Multi-host scaling: ``FLEngine(..., shard_trials=True)`` lays the
(embarrassingly parallel) trials axis over all visible devices with
``shard_map`` — a flag, not a rewrite; trials must divide the device count.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..core import async_fl
from ..core import baselines as B
from ..core import participation as participation_lib
from ..core import rngstream
from ..core.channel import Deployment, sample_fading_batch, sample_fading_jax
from ..core.digital import (capacity_rate_jnp, digital_round_jax,
                            greedy_bit_alloc_jax, outage_mask, topk_mask)
from ..core.faults import FaultSpec, fault_masks, survival_prob
from ..core.ota import bbfl_round_jax, opc_ota_fl_round_jax, ota_round_jax
from ..core.quantize import payload_bits
from ..kernels import ops
from .tasks import jit_f32
from .trainer import TrainLog

#: AggregatorFn protocol: (grads (N,d) f32, h (N,) complex64, z01 (d,) f32,
#: u (N,d) f32 dither, sel (S,) f32 replayed selection draws, t int) ->
#: (ghat (d,), latency scalar). Latency is in channel uses for OTA schemes
#: (converted to seconds by the engine via 1/B) and in seconds for digital
#: schemes, matching ``core.baselines.RoundResult``. ``t`` carries the round
#: index for parity-scheduled schemes (BB-FL Alternative's ``t % 2``).
AggregatorFn = Callable[..., tuple]


@dataclasses.dataclass(eq=False)
class JaxAggregator:
    """A wireless aggregation scheme in functional form.

    ``round_fn`` must be pure and jit/vmap/scan-able; scheme constants
    (pre-scalers, thresholds, post-scalers) are baked in as closure
    constants, mirroring the paper's offline-designed, time-invariant
    parameters.
    """

    name: str
    is_ota: bool
    round_fn: AggregatorFn
    needs_noise: bool = True
    needs_dither: bool = False
    # (seed, trial, T) -> (T, S) float64 replay of the per-round selection
    # draws the NumPy scheme consumes from the sequential trial rng (see
    # core.rngstream.replay_rounds); None when the scheme draws none
    sel_stream_np: Optional[Callable[[int, int, int], np.ndarray]] = None
    # fast-mode analog of sel_stream_np: (round-folded threefry key) ->
    # (S,) float32 row with the exact layout ``round_fn`` consumes, drawn
    # in-scan from the SELECT_TAG stream. None when the scheme draws no
    # selection randomness; a scheme with sel_stream_np but no fast
    # sampler rejects rng="fast" instead of silently diverging
    sel_stream_jax: Optional[Callable] = None
    # jitted trial runners keyed on (task id, shapes, schedule); kept on the
    # aggregator so step-size grid searches across trainer instances reuse
    # the compiled scan
    _runner_cache: dict = dataclasses.field(default_factory=dict, repr=False)


# ------------------------------------------------------------ port registry

#: Routing table: NumPy Aggregator type -> functional port factory. The
#: trainer's backend="auto" consults this (via ``as_functional``) instead of
#: a hard-coded fallback list; registering a port here is all it takes to
#: route a new scheme through the engine.
_PORT_FACTORIES: dict = {}


def register_port(cls):
    def deco(factory):
        _PORT_FACTORIES[cls] = factory
        return factory
    return deco


# ------------------------------------------------------- OTA scheme ports

@register_port(B.IdealFedAvg)
def _ideal_fedavg(agg, use_kernel: bool) -> JaxAggregator:
    def round_fn(grads, h, z01, u, sel, t):
        return jnp.mean(grads, axis=0), 0.0

    return JaxAggregator(name=agg.name, is_ota=True,
                         round_fn=round_fn, needs_noise=False)


def _from_ota_params(agg, use_kernel: bool) -> JaxAggregator:
    params = agg.params

    def round_fn(grads, h, z01, u, sel, t):
        ghat, _ = ota_round_jax(params, grads, h, z01, use_kernel=use_kernel)
        return ghat, float(params.dim)

    return JaxAggregator(name=agg.name, is_ota=True, round_fn=round_fn)


register_port(B.ProposedOTA)(_from_ota_params)
register_port(B.LCPCOTAComp)(_from_ota_params)


@register_port(B.VanillaOTA)
def _vanilla_ota(agg: "B.VanillaOTA", use_kernel: bool) -> JaxAggregator:
    dim, g_max, e_s, n0 = agg.dim, agg.g_max, agg.e_s, agg.n0
    root_des = np.sqrt(dim * e_s)
    root_n0 = np.sqrt(n0)

    def round_fn(grads, h, z01, u, sel, t):
        n = grads.shape[0]
        gamma_t = root_des * jnp.min(jnp.abs(h)) / g_max
        acc = gamma_t * jnp.sum(grads, axis=0)
        ghat = ops.ota_combine_with_noise(acc, n * gamma_t, root_n0 * z01,
                                          use_kernel=use_kernel)
        return ghat, float(dim)

    return JaxAggregator(name=agg.name, is_ota=True, round_fn=round_fn)


@register_port(B.OPCOTAComp)
def _opc_ota_comp(agg: "B.OPCOTAComp", use_kernel: bool) -> JaxAggregator:
    dim, g_max, e_s, n0 = agg.dim, agg.g_max, agg.e_s, agg.n0
    n_grid = agg.n_grid
    b_bar = np.sqrt(dim * e_s) / g_max
    root_n0 = np.sqrt(n0)

    def round_fn(grads, h, z01, u, sel, t):
        habs = jnp.abs(h)
        n = grads.shape[0]
        lo = jnp.maximum((b_bar * jnp.min(habs)) ** 2 * 1e-4, 1e-37)
        hi = (b_bar * jnp.max(habs)) ** 2 * 1e4
        etas = jnp.geomspace(lo, hi, n_grid)                       # (n_grid,)
        b = jnp.minimum(b_bar, jnp.sqrt(etas)[:, None] / habs)     # (n_grid,N)
        c = b * habs / jnp.sqrt(etas)[:, None]
        mses = (g_max ** 2 * jnp.sum((c - 1.0) ** 2, axis=1) / n ** 2
                + dim * n0 / (n ** 2 * etas))
        eta = etas[jnp.argmin(mses)]
        b_t = jnp.minimum(b_bar, jnp.sqrt(eta) / habs)
        acc = (b_t * habs) @ grads
        ghat = ops.ota_combine_with_noise(acc, n * jnp.sqrt(eta),
                                          root_n0 * z01,
                                          use_kernel=use_kernel)
        return ghat, float(dim)

    return JaxAggregator(name=agg.name, is_ota=True, round_fn=round_fn)


@register_port(B.OPCOTAFL)
def _opc_ota_fl(agg: "B.OPCOTAFL", use_kernel: bool) -> JaxAggregator:
    dim, g_max, e_s, n0 = agg.dim, agg.g_max, agg.e_s, agg.n0

    def round_fn(grads, h, z01, u, sel, t):
        ghat, _ = opc_ota_fl_round_jax(grads, h, z01, dim=dim, g_max=g_max,
                                       e_s=e_s, n0=n0, use_kernel=use_kernel)
        return ghat, float(dim)

    return JaxAggregator(name=agg.name, is_ota=True, round_fn=round_fn)


@register_port(B.BBFLInterior)
def _bbfl_interior(agg: "B.BBFLInterior", use_kernel: bool) -> JaxAggregator:
    interior = np.asarray(agg.interior, dtype=np.float64)
    dim, g_max, e_s, n0 = agg.dim, agg.g_max, agg.e_s, agg.n0

    def round_fn(grads, h, z01, u, sel, t):
        ghat, _ = bbfl_round_jax(grads, h, z01, t, dim=dim, g_max=g_max,
                                 e_s=e_s, n0=n0,
                                 gamma_odd=agg.gamma, mask_odd=interior,
                                 gamma_even=agg.gamma, mask_even=interior,
                                 use_kernel=use_kernel)
        return ghat, float(dim)

    return JaxAggregator(name=agg.name, is_ota=True, round_fn=round_fn)


@register_port(B.BBFLAlternative)
def _bbfl_alternative(agg: "B.BBFLAlternative",
                      use_kernel: bool) -> JaxAggregator:
    interior = np.asarray(agg.interior_agg.interior, dtype=np.float64)
    all_mask = np.asarray(agg.all_mask, dtype=np.float64)
    dim, g_max, e_s, n0 = agg.dim, agg.g_max, agg.e_s, agg.n0

    def round_fn(grads, h, z01, u, sel, t):
        ghat, _ = bbfl_round_jax(
            grads, h, z01, t, dim=dim, g_max=g_max, e_s=e_s, n0=n0,
            gamma_odd=agg.interior_agg.gamma, mask_odd=interior,
            gamma_even=agg.gamma_all, mask_even=all_mask,
            use_kernel=use_kernel)
        return ghat, float(dim)

    return JaxAggregator(name=agg.name, is_ota=True, round_fn=round_fn)


# --------------------------------------------------- digital scheme ports

@register_port(B.ProposedDigital)
def _proposed_digital(agg, use_kernel: bool) -> JaxAggregator:
    params = agg.params

    def round_fn(grads, h, z01, u, sel, t):
        ghat, _, latency = digital_round_jax(params, grads, h, u,
                                             use_kernel=use_kernel)
        return ghat, latency

    return JaxAggregator(name=agg.name, is_ota=False, round_fn=round_fn,
                         needs_noise=False, needs_dither=True)


def _quantized_mean(grads, chi, bits, u, k, use_kernel, r_max=None):
    """sum_{m in sel} dequant(quant(g_m, r_m)) / k and the payload levels.

    ``r_max``: the scheme's static upper bound on any device's bit-width —
    lets the payload-scale fused pack path (quantize straight into a
    uint32 code buffer, O(d) dequant-accumulate) kick in at large d.
    """
    levels = chi * (jnp.exp2(bits) - 1.0)
    return ops.quantized_weighted_sum(grads, levels, u, chi / k,
                                      r_max=r_max, use_kernel=use_kernel)


@register_port(B.BestChannel)
def _best_channel(agg: "B.BestChannel", use_kernel: bool) -> JaxAggregator:
    dim, e_s, n0, bw = agg.dim, agg.e_s, agg.n0, agg.B
    k, r = agg.k, agg.r
    payload = float(payload_bits(dim, r))

    def round_fn(grads, h, z01, u, sel, t):
        habs = jnp.abs(h)
        chi = topk_mask(habs, k).astype(grads.dtype)
        rate = capacity_rate_jnp(habs, e_s, n0)
        lat = jnp.sum(chi * payload / (bw * jnp.maximum(rate, 1e-9)))
        acc = _quantized_mean(grads, chi, chi * r, u, k, use_kernel,
                              r_max=r)
        return acc, lat

    return JaxAggregator(name=agg.name, is_ota=False, round_fn=round_fn,
                         needs_noise=False, needs_dither=True)


@register_port(B.BestChannelNorm)
def _best_channel_norm(agg: "B.BestChannelNorm",
                       use_kernel: bool) -> JaxAggregator:
    dim, e_s, n0, bw = agg.dim, agg.e_s, agg.n0, agg.B
    k, kp, r_total = agg.k, agg.kp, agg.r_total

    def round_fn(grads, h, z01, u, sel, t):
        habs = jnp.abs(h)
        cand = topk_mask(habs, kp)
        # per-device scoring through the fused Pallas row reduction
        _, sumsq = ops.row_maxabs_sumsq(grads, use_kernel=use_kernel)
        norms = jnp.sqrt(sumsq)
        chi = topk_mask(jnp.where(cand > 0, norms, -jnp.inf), k
                        ).astype(grads.dtype)
        share = (chi * norms) / jnp.maximum(jnp.sum(chi * norms), 1e-12)
        bits = chi * jnp.maximum(1.0, jnp.round(r_total * share))
        rate = capacity_rate_jnp(habs, e_s, n0)
        lat = jnp.sum(chi * (64.0 + dim * bits)
                      / (bw * jnp.maximum(rate, 1e-9)))
        acc = _quantized_mean(grads, chi, bits, u, k, use_kernel,
                              r_max=r_total)
        return acc, lat

    return JaxAggregator(name=agg.name, is_ota=False, round_fn=round_fn,
                         needs_noise=False, needs_dither=True)


@register_port(B.PropFairness)
def _prop_fairness(agg: "B.PropFairness", use_kernel: bool) -> JaxAggregator:
    dim, e_s, n0, bw = agg.dim, agg.e_s, agg.n0, agg.B
    k, r = agg.k, agg.r
    lambdas = np.asarray(agg.dep.lambdas)
    payload = float(payload_bits(dim, r))

    def round_fn(grads, h, z01, u, sel, t):
        habs = jnp.abs(h)
        chi = topk_mask(habs ** 2 / lambdas, k).astype(grads.dtype)
        rate = capacity_rate_jnp(habs, e_s, n0)
        lat = jnp.sum(chi * payload / (bw * jnp.maximum(rate, 1e-9)))
        acc = _quantized_mean(grads, chi, chi * r, u, k, use_kernel,
                              r_max=r)
        return acc, lat

    return JaxAggregator(name=agg.name, is_ota=False, round_fn=round_fn,
                         needs_noise=False, needs_dither=True)


@register_port(B.UQOS)
def _uqos(agg: "B.UQOS", use_kernel: bool) -> JaxAggregator:
    dim, e_s, n0, bw = agg.dim, agg.e_s, agg.n0, agg.B
    k, r, rate_c = agg.k, agg.r, agg.rate
    pi = np.asarray(agg.pi)
    p_succ = np.asarray(agg.p_succ)
    n = pi.shape[0]
    payload = float(payload_bits(dim, r))

    def sel_stream(seed, trial, T):
        # per round: sampling permutation + inclusion keys, in draw order
        def draw(rng):
            return np.concatenate([rng.permutation(n).astype(np.float64),
                                   rng.uniform(size=n)])
        return rngstream.replay_rounds(seed, trial, T, draw)

    def sel_stream_jax(key):
        # same row layout as the replay draw: permutation then uniforms
        kp, ku = jax.random.split(key)
        return jnp.concatenate([
            jax.random.permutation(kp, n).astype(jnp.float32),
            jax.random.uniform(ku, (n,), dtype=jnp.float32)])

    def round_fn(grads, h, z01, u, sel, t):
        order = sel[:n].astype(jnp.int32)
        keys = sel[n:] ** (1.0 / jnp.asarray(pi)[order])
        chosen = order[jnp.argsort(keys)[::-1][:k]]
        cmask = jnp.zeros(n, grads.dtype).at[chosen].set(1.0)
        habs = jnp.abs(h)
        snr_ok = capacity_rate_jnp(habs, e_s, n0) >= rate_c
        active = cmask * snr_ok
        levels = active * (2.0 ** r - 1.0)
        acc = ops.quantized_weighted_sum(            # unbiased reweight
            grads, levels, u, active / (n * pi * p_succ),
            r_max=r, use_kernel=use_kernel)
        lat = jnp.sum(active) * payload / (bw * rate_c)
        return acc, lat

    return JaxAggregator(name=agg.name, is_ota=False, round_fn=round_fn,
                         needs_noise=False, needs_dither=True,
                         sel_stream_np=sel_stream,
                         sel_stream_jax=sel_stream_jax)


@register_port(B.QML)
def _qml(agg: "B.QML", use_kernel: bool) -> JaxAggregator:
    dim, e_s, n0, bw = agg.dim, agg.e_s, agg.n0, agg.B
    k = agg.k
    n = agg.dep.n_devices
    # smallest r meeting the per-device variance cap (static, as the oracle)
    r = 1
    while (dim * agg.g_max ** 2 / (2.0 ** r - 1.0) ** 2 > agg.var_cap
           and r < agg.r_max):
        r += 1
    payload = float(payload_bits(dim, r))

    def sel_stream(seed, trial, T):
        return rngstream.replay_rounds(
            seed, trial, T, lambda rng: rng.choice(n, size=k, replace=False))

    def sel_stream_jax(key):
        return jax.random.choice(key, n, (k,),
                                 replace=False).astype(jnp.float32)

    def round_fn(grads, h, z01, u, sel, t):
        chi = jnp.zeros(n, grads.dtype).at[sel.astype(jnp.int32)].set(1.0)
        rate = capacity_rate_jnp(jnp.abs(h), e_s, n0)
        lat = jnp.sum(chi * payload / (bw * jnp.maximum(rate, 1e-9)))
        acc = _quantized_mean(grads, chi, chi * r, u, k, use_kernel,
                              r_max=r)
        return acc, lat

    return JaxAggregator(name=agg.name, is_ota=False, round_fn=round_fn,
                         needs_noise=False, needs_dither=True,
                         sel_stream_np=sel_stream,
                         sel_stream_jax=sel_stream_jax)


@register_port(B.FedTOE)
def _fedtoe(agg: "B.FedTOE", use_kernel: bool) -> JaxAggregator:
    dim, bw = agg.dim, agg.B
    k, p_out, t_budget, r_max = agg.k, agg.p_out, agg.t_budget, agg.r_max
    rates = np.asarray(agg.rates)
    thr = np.asarray(agg.thr)
    n = rates.shape[0]

    def sel_stream(seed, trial, T):
        return rngstream.replay_rounds(
            seed, trial, T, lambda rng: rng.choice(n, size=k, replace=False))

    def sel_stream_jax(key):
        return jax.random.choice(key, n, (k,),
                                 replace=False).astype(jnp.float32)

    def round_fn(grads, h, z01, u, sel, t):
        bits, in_alloc = greedy_bit_alloc_jax(
            sel.astype(jnp.int32), jnp.asarray(rates), dim=dim,
            bandwidth_hz=bw, t_budget_s=t_budget, r_max=r_max)
        lat = jnp.sum(in_alloc * (64.0 + dim * bits)
                      / (bw * jnp.maximum(rates, 1e-9)))
        chi = (in_alloc * outage_mask(jnp.abs(h), thr)).astype(grads.dtype)
        k_sched = jnp.maximum(jnp.sum(in_alloc), 1.0)
        acc = _quantized_mean(grads, chi, chi * bits, u,
                              k_sched * (1.0 - p_out), use_kernel,
                              r_max=r_max)
        return acc, lat

    return JaxAggregator(name=agg.name, is_ota=False, round_fn=round_fn,
                         needs_noise=False, needs_dither=True,
                         sel_stream_np=sel_stream,
                         sel_stream_jax=sel_stream_jax)


def as_functional(agg, use_kernel: bool = True) -> Optional[JaxAggregator]:
    """Functional port of a NumPy ``Aggregator`` instance, or None when the
    scheme has no registered port (the trainer then falls back to NumPy).

    Ports are resolved through the ``_PORT_FACTORIES`` routing table and
    memoized on the aggregator instance so repeated runs (e.g. the
    benchmarks' step-size grid search) share compiled scans.
    """
    if isinstance(agg, JaxAggregator):
        return agg
    cache = agg.__dict__.setdefault("_jax_ports", {})
    if use_kernel in cache:
        return cache[use_kernel]
    factory = _PORT_FACTORIES.get(type(agg))
    port = factory(agg, use_kernel) if factory is not None else None
    cache[use_kernel] = port
    return port


# ----------------------------------------------------------------- engine

def _project(w, radius):
    nrm = jnp.linalg.norm(w)
    scale = jnp.minimum(1.0, radius / jnp.maximum(nrm, 1e-30))
    return w * scale


class FLEngine:
    """vmap(lax.scan) Monte-Carlo FL simulator (same protocol as FLTrainer).

    One jitted call runs all trials of all rounds: fading/noise/selection
    draws come in as batched (trials, T, ...) tensors, quantization dither
    and mini-batch indices stream from scan-carried per-trial keys (O(N*d)
    per round), rounds advance under a two-level ``lax.scan`` (outer: eval
    segments, inner: rounds) so only the model states at eval points are
    materialized, time budgets freeze the carry in-scan once the cumulative
    wall-clock is spent, and trials are batched with ``vmap`` — including
    through the Pallas epilogue kernels — or laid over devices with
    ``shard_map`` when ``shard_trials=True``.
    """

    def __init__(self, task, dataset, deployment: Deployment, eta: float, *,
                 project_radius: Optional[float] = None,
                 batch_size: Optional[int] = None,
                 use_kernel: bool = True, shard_trials: bool = False,
                 payload_dtype: str = "f32",
                 fault: Optional[FaultSpec] = None,
                 clients_per_round: Optional[int] = None,
                 participation: str = "uniform",
                 participation_probs=None,
                 mode: str = "sync",
                 async_spec: Optional[async_fl.AsyncSpec] = None,
                 async_weights=None):
        if payload_dtype not in ("f32", "bf16"):
            raise ValueError(
                f"payload_dtype must be 'f32' or 'bf16', got {payload_dtype!r}")
        self.task = task
        self.ds = dataset
        self.dep = deployment
        self.eta = eta
        self.project_radius = project_radius
        self.use_kernel = use_kernel
        self.shard_trials = shard_trials
        self.payload_dtype = payload_dtype
        # a disabled FaultSpec normalizes to None: the scan traces the
        # exact pre-fault program, so disabled-fault runs are bit-identical
        self.fault = fault if fault is not None and fault.enabled else None
        # clients_per_round=None likewise normalizes to None (strict
        # no-op); otherwise the validated sampling config is shared with
        # the oracle bit-for-bit (core.participation). The loss/datasize
        # policies derive their capped-simplex weights from (task,
        # dataset) — pure NumPy, identical bits on both backends.
        part_weights = None
        if (clients_per_round is not None and participation_probs is None
                and participation in participation_lib.WEIGHTED_POLICIES):
            part_weights = participation_lib.policy_weights(
                participation, task, dataset)
        self.participation = participation_lib.resolve(
            clients_per_round, participation, participation_probs,
            n_devices=deployment.n_devices, lambdas=deployment.lambdas,
            weights=part_weights)
        # mode="sync" normalizes to None the same way: the scan traces
        # the exact pre-async program (strict no-op). The resolved tables
        # (rates/CDF/discounts/weights) are float64 tuples shared with
        # the oracle bit-for-bit (core.async_fl).
        self.async_ = async_fl.resolve(mode, async_spec,
                                       deployment.n_devices, async_weights)
        sizes = tuple(len(d) for d in dataset.devices)
        if len(set(sizes)) == 1:
            self.device_sizes = None      # equal sizes: plain stacked arrays
            self.batch_size = self.effective_batch_size(batch_size, sizes[0])
            self.xs = np.stack(
                [d.x for d in dataset.devices]).astype(np.float32)
            self.ys = np.stack(
                [d.y for d in dataset.devices]).astype(np.int32)
        else:
            # unequal sizes: zero-pad each device to n_max and regenerate
            # per-device batch indices in-scan. Strictly mini-batch rounds
            # (batch_size < every size) use batch_block_ragged, whose
            # per-device keyed draws match the oracle's batch_indices_np
            # exactly and never touch the padding rows; the mixed
            # full/mini-batch regime (batch_size >= some device's size)
            # runs those devices full-batch through the weighted gradient
            # path (see _get_runner).
            if batch_size is None:
                raise ValueError(
                    "FLEngine needs a mini-batch size when device datasets "
                    f"have unequal sizes (got sizes {sorted(set(sizes))}); "
                    "use backend='numpy' for full-batch unequal runs")
            self.device_sizes = sizes
            self.batch_size = batch_size
            n_max = max(sizes)
            d0 = dataset.devices[0]
            xs = np.zeros((len(sizes), n_max) + d0.x.shape[1:], np.float32)
            ys = np.zeros((len(sizes), n_max), np.int32)
            for m, dd in enumerate(dataset.devices):
                xs[m, :len(dd)] = dd.x
                ys[m, :len(dd)] = dd.y
            self.xs, self.ys = xs, ys
        self.x_all = np.concatenate(
            [d.x for d in dataset.devices]).astype(np.float32)
        self.y_all = np.concatenate(
            [d.y for d in dataset.devices]).astype(np.int32)
        self.x_test = np.asarray(dataset.x_test, np.float32)
        self.y_test = np.asarray(dataset.y_test, np.int32)
        # built once so repeated run() calls hit the jit cache
        self._loss_v = jit_f32(jax.vmap(task.loss_fn,
                                         in_axes=(0, None, None)))
        self._acc_v = jit_f32(jax.vmap(task.accuracy_fn,
                                        in_axes=(0, None, None)))

    @staticmethod
    def effective_batch_size(batch_size: Optional[int],
                             n_data: int) -> Optional[int]:
        """batch_size >= |D_m| is full-batch (DeviceDataset.batch
        semantics). The single normalization rule shared with the trainer's
        engine-cache comparison."""
        return (None if batch_size is not None and batch_size >= n_data
                else batch_size)

    # ------------------------------------------------------- scan runner

    def _get_runner(self, jagg: JaxAggregator, trials: int, n_seg: int,
                    eval_every: int, rng_mode: str):
        d, N = self.task.dim, self.dep.n_devices
        if (rng_mode == "fast" and jagg.sel_stream_np is not None
                and jagg.sel_stream_jax is None):
            raise ValueError(
                f"{jagg.name} consumes selection randomness but its JAX "
                "port has no fast-mode sampler (sel_stream_jax); use "
                "rng='replay'")
        # the task object itself keys (and pins) the gradient function;
        # everything else closed over by trial_fn is shape-static, and all
        # run-varying scalars (eta, radius, lat_div, budget) are traced
        # arguments
        key = (self.task, trials, n_seg, eval_every, d, N,
               self.xs.shape, self.batch_size, self.device_sizes,
               self.use_kernel, self.shard_trials, rng_mode,
               self.payload_dtype, self.fault, self.participation,
               self.async_)
        if key in jagg._runner_cache:
            return jagg._runner_cache[key]

        batch_size = self.batch_size
        device_sizes = self.device_sizes
        n_data = self.xs.shape[1]
        # mixed full/mini-batch regime: unequal device sizes with the batch
        # covering some devices. Covered devices run full-batch; the batch
        # block still has batch_size columns (gather rows are clipped), so
        # per-row *weights* carry each device's true normalization — full
        # rows weight their n_m real rows by 1/n_m (clipped duplicates get
        # 0), mini rows weight by 1/batch_size — through the task's
        # weighted gradient path.
        mixed = (device_sizes is not None
                 and batch_size >= min(device_sizes))
        if batch_size is None:
            grads_fn = self.task.device_grads_fn
        elif mixed:
            grads_fn = self.task.device_grads_at_weighted_fn
            wts = np.zeros((N, batch_size), np.float32)
            for m, n_m in enumerate(device_sizes):
                if n_m <= batch_size:
                    wts[m, :n_m] = 1.0 / n_m
                else:
                    wts[m, :] = 1.0 / batch_size
            batch_wts = jnp.asarray(wts)
        else:
            grads_fn = self.task.device_grads_at_fn
        payload_bf16 = self.payload_dtype == "bf16"
        round_fn = jagg.round_fn
        needs_dither = jagg.needs_dither
        needs_noise = jagg.needs_noise
        sel_jax = jagg.sel_stream_jax
        has_sel = jagg.sel_stream_np is not None
        fast = rng_mode == "fast"
        lambdas = jnp.asarray(self.dep.lambdas, jnp.float32)
        # fault layer: trace-time static — with faults disabled (None) the
        # scan below is the exact pre-fault program (bit-identical runs)
        fault = self.fault
        stale = fault is not None and fault.on_missing == "stale"
        if fault is not None:
            q_surv = jnp.asarray(
                survival_prob(fault, np.asarray(self.dep.lambdas)),
                jnp.float32)
            has_deadline = fault.deadline_s is not None
            deadline = float(fault.deadline_s) if has_deadline else np.inf
            straggler_mult = float(fault.straggler_mult)
        # participation layer: trace-time static like the fault layer —
        # with clients_per_round=None the scan below is the exact
        # pre-participation program (bit-identical runs)
        part = self.participation
        if part is not None:
            part_probs = jnp.asarray(part.probs_array(), jnp.float32)
            part_scale = float(part.scale)
        # buffered-async layer: trace-time static like the fault and
        # participation layers — with mode="sync" (None) the scan below is
        # the exact pre-async program (bit-identical runs). All tables are
        # precomputed host-side float64 and rounded to f32 here; the
        # oracle compares against the same rounded values, so the in-scan
        # realization is exact comparisons/gathers only (bit-identical).
        asy = self.async_
        amode = asy is not None
        if amode:
            a_stale = asy.on_missing == "stale"
            a_k = asy.buffer_rounds
            a_rates = jnp.asarray(asy.rates_array(), jnp.float32)
            a_cdf = jnp.asarray(asy.cdf_array(), jnp.float32)
            a_disc = jnp.asarray(asy.discounts_array(), jnp.float32)
            a_pscale = jnp.asarray(asy.payload_scale_array(), jnp.float32)
        else:
            a_stale = False

        def trial_fn(w0, eta, radius, lat_div, budget, xs, ys, dkey, bkey,
                     fkey, pkey, akey, A, B_, C, Ts):
            # dkey/bkey/fkey/pkey/akey: scan-carried / closed-over
            # per-trial dither, batch-index, fault-, participation- and
            # arrival-stream keys (counter-based in both modes).
            # replay: A=H (n_seg, eval_every, N) complex, B_=Z
            # (n_seg, eval_every, dz), C=SEL (n_seg, eval_every, S) — host
            # precomputed tensors fed through the scan.
            # fast: A/B_/C are the trial's fading/noise/selection threefry
            # base keys (uint32 (2,)); every draw is regenerated in-scan
            # from (key, t), so nothing is precomputed and Ts is the only
            # scan input. Same arity either way, so the vmap/shard_map
            # plumbing below is mode-blind.
            def step(carry, inp):
                # fixed base carry + trace-time-static optional extras, in
                # order: [async last-K buffer, async last-delivered
                # payloads, fault "stale" last-received gradients]
                w, t_wall, _, dkey, bkey = carry[:5]
                ext = list(carry[5:])
                if amode:
                    a_buf = ext.pop(0)
                    if a_stale:
                        g_alast = ext.pop(0)
                if stale:
                    # "stale" carries the last *received* per-device
                    # gradients so missing payloads replay them
                    g_stale = ext.pop(0)
                if fast:
                    t = inp
                    h = sample_fading_jax(A, t, lambdas)
                    z = (rngstream.noise_block(B_, t, d) if needs_noise
                         else jnp.zeros((1,), jnp.float32))
                    selrow = (sel_jax(jax.random.fold_in(C, t)) if has_sel
                              else jnp.zeros((1,), jnp.float32))
                else:
                    h, z, selrow, t = inp
                # the trainer breaks on the first round whose *preceding*
                # cumulative wall-clock hit the budget; past that round the
                # carry freezes (w and t_wall stop advancing)
                active = t_wall < budget
                if batch_size is None:
                    g = grads_fn(w, xs, ys)
                else:
                    # (N, B) counter-based indices regenerated in-scan —
                    # bit-identical to the oracle's batch_block_np /
                    # batch_indices_np draws (ragged rows key on each
                    # device's own size and never hit the padding)
                    if mixed:
                        idx = rngstream.batch_block_mixed(
                            bkey, t, device_sizes, batch_size)
                        g = grads_fn(w, xs, ys, idx, batch_wts)
                    elif device_sizes is not None:
                        idx = rngstream.batch_block_ragged(
                            bkey, t, device_sizes, batch_size)
                        g = grads_fn(w, xs, ys, idx)
                    else:
                        idx = rngstream.batch_block(bkey, t, N, n_data,
                                                    batch_size)
                        g = grads_fn(w, xs, ys, idx)
                if payload_bf16:
                    # mixed-precision uplink: the gradient payload leaves
                    # the device truncated to bf16; aggregation stays in
                    # the engine's f32 accumulators
                    g = g.astype(jnp.bfloat16).astype(jnp.float32)
                if part is not None:
                    # Bernoulli client sampling (counter-based PARTICIPATE
                    # stream, bit-identical across backends/rng modes):
                    # excluded payloads zero out, included ones carry the
                    # uniform inverse-propensity scale N/S — applied
                    # upstream of the fault layer and the scheme's
                    # combiner (non-participants keep their reserved
                    # slots, like faulted devices)
                    up = rngstream.participation_block(pkey, t, N)
                    chi = up < part_probs
                    g = g * (chi.astype(jnp.float32) * part_scale)[:, None]
                if amode:
                    # buffered-async delivery (counter-based ARRIVAL
                    # stream, bit-identical across backends/rng modes):
                    # the last-K buffer shifts, each device delivers a
                    # staleness-S discounted payload drawn against the
                    # precomputed rate/CDF tables, and missing devices
                    # zero-fill or replay their last delivered payload —
                    # applied upstream of the fault layer and the
                    # scheme's combiner, like the layers around it
                    ua = rngstream.arrival_block(akey, t, N)
                    g, ok_a, a_buf = async_fl.async_round(
                        g, a_buf, ua, a_rates, a_cdf, a_disc, a_pscale)
                    if a_stale:
                        g, g_alast = async_fl.stale_replace(g, ok_a,
                                                            g_alast)
                    else:
                        g = g * ok_a.astype(jnp.float32)[:, None]
                if fault is not None:
                    # counter-based fault draws + degradation policy,
                    # applied to the payloads *upstream* of the scheme's
                    # combiner so every registered port inherits it
                    # (faulted devices keep their reserved slots; a zeroed
                    # payload quantizes to exact zeros on both backends)
                    uf = rngstream.fault_block(fkey, t, N)
                    okb, straggler = fault_masks(uf, jnp.abs(h), fault)
                    if fault.on_missing == "zero":
                        g = g * okb.astype(jnp.float32)[:, None]
                    elif fault.on_missing == "reweight":
                        g = g * (okb.astype(jnp.float32) / q_surv)[:, None]
                    else:
                        # stale: replay the last received gradient — the
                        # single last-gradient code path shared with the
                        # async buffer (core.async_fl)
                        g, g_stale = async_fl.stale_replace(g, okb,
                                                            g_stale)
                if needs_dither:
                    # one (N, d) block regenerated per round — the whole
                    # dither stream never exists in memory at once
                    u = rngstream.dither_block(dkey, t, N, d)
                else:
                    u = jnp.zeros((1, 1), jnp.float32)
                ghat, lat = round_fn(g, h, z, u, selrow, t)
                # division (not reciprocal-multiply) so OTA wall-clock is
                # bit-equal to the trainer's ``latency_s / bandwidth`` and
                # budget comparisons freeze on the same round
                w_new = jnp.where(active, _project(w - eta * ghat, radius), w)
                if fault is not None:
                    # delivering stragglers stretch the round; a deadline
                    # instead caps it (stragglers then miss via the mask)
                    lat_s = lat / lat_div
                    slow = jnp.any(straggler & okb)
                    lat_s = jnp.where(slow, lat_s * straggler_mult, lat_s)
                    if has_deadline:
                        lat_s = jnp.minimum(lat_s, deadline)
                    t_wall = jnp.where(active, t_wall + lat_s, t_wall)
                else:
                    t_wall = jnp.where(active, t_wall + lat / lat_div,
                                       t_wall)
                out = (w_new, t_wall, active, dkey, bkey)
                if amode:
                    out = out + (a_buf,)
                    if a_stale:
                        out = out + (g_alast,)
                if stale:
                    out = out + (g_stale,)
                return out, None

            def segment(carry, seg_inp):
                w_eval, inner = carry[0], carry[1:]
                inner, _ = jax.lax.scan(step, inner, seg_inp)
                w, t_wall, live = inner[0], inner[1], inner[2]
                # the eval at this segment's end is written by the trainer
                # iff the segment's last round still ran; otherwise the slot
                # freezes at the last written eval state
                w_eval = jnp.where(live, w, w_eval)
                return (w_eval,) + inner, (w_eval, t_wall)

            carry0 = (w0, w0, jnp.zeros((), jnp.float32),
                      jnp.asarray(True), dkey, bkey)
            if amode:
                # pre-start buffer slots are zeros: a staleness draw that
                # reaches past round 0 delivers nothing (the device had
                # not computed yet), matching the oracle exactly
                carry0 = carry0 + (jnp.zeros((a_k, N, d), jnp.float32),)
                if a_stale:
                    carry0 = carry0 + (jnp.zeros((N, d), jnp.float32),)
            if stale:
                # until a device's first delivery, "stale" replays zeros
                carry0 = carry0 + (jnp.zeros((N, d), jnp.float32),)
            seg_xs = Ts if fast else (A, B_, C, Ts)
            _, (ws, walls) = jax.lax.scan(segment, carry0, seg_xs)
            ws = jnp.concatenate([w0[None], ws], axis=0)          # (E, d)
            walls = jnp.concatenate([jnp.zeros((1,), walls.dtype), walls],
                                    axis=0)
            return ws, walls

        vmapped = jax.vmap(
            trial_fn,
            in_axes=(None, None, None, None, None, None, None,
                     0, 0, 0, 0, 0, 0, 0, 0, None))
        if self.shard_trials:
            n_hw = len(jax.devices())
            if trials % n_hw != 0:
                raise ValueError(
                    f"shard_trials needs trials ({trials}) divisible by the "
                    f"device count ({n_hw})")
            mesh = jax.make_mesh((n_hw,), ("trials",))
            P = jax.sharding.PartitionSpec
            vmapped = jax.shard_map(
                vmapped, mesh=mesh,
                in_specs=(P(), P(), P(), P(), P(), P(), P(),
                          P("trials"), P("trials"), P("trials"), P("trials"),
                          P("trials"), P("trials"), P("trials"), P("trials"),
                          P()),
                out_specs=(P("trials"), P("trials")), check_vma=False)
        runner = jit_f32(vmapped)
        jagg._runner_cache[key] = runner
        return runner

    # --------------------------------------------------------------- run

    def run(self, aggregator, *, rounds: int, trials: int = 3,
            eval_every: int = 10, seed: int = 0,
            w_star: Optional[np.ndarray] = None,
            time_budget_s: Optional[float] = None,
            rng: str = "replay") -> TrainLog:
        jagg, runner, args = self.prepare(
            aggregator, rounds=rounds, trials=trials, eval_every=eval_every,
            seed=seed, time_budget_s=time_budget_s, rng=rng)
        ws, walls = runner(*args)
        losses, accs = self._evaluate(ws)
        opt_err = (np.sum((np.asarray(ws, np.float64) - w_star) ** 2,
                          axis=-1)
                   if w_star is not None else None)
        return TrainLog(scheme=jagg.name,
                        rounds=np.arange(0, rounds + 1, eval_every,
                                         dtype=np.int64),
                        wall_time_s=np.asarray(walls).mean(axis=0),
                        global_loss=np.asarray(losses, np.float64),
                        accuracy=np.asarray(accs, np.float64),
                        opt_error=opt_err, quantized=not jagg.is_ota)

    def prepare(self, aggregator, *, rounds: int, trials: int = 3,
                eval_every: int = 10, seed: int = 0,
                time_budget_s: Optional[float] = None,
                rng: str = "replay"):
        """The jitted scan of one :meth:`run` and its arguments:
        ``(jagg, runner, args)``. ``runner(*args)`` is the run (model
        states and wall-clock at every eval point); ``runner.lower(*args)``
        is its program, for counting its kernels or compiling it for a
        described chip."""
        if rng not in ("replay", "fast"):
            raise ValueError(f"rng must be 'replay' or 'fast', got {rng!r}")
        jagg = as_functional(aggregator, use_kernel=self.use_kernel)
        if jagg is None:
            raise ValueError(
                f"no JAX port for {type(aggregator).__name__}; "
                "use FLTrainer.run(..., backend='numpy')")
        eval_rounds = list(range(0, rounds + 1, eval_every))
        n_seg = len(eval_rounds) - 1
        T = n_seg * eval_every      # rounds past the last eval are unobserved
        d, N = self.task.dim, self.dep.n_devices

        if rng == "fast":
            # zero host-side precompute: only three (2,)-uint32 base keys
            # per trial; fading/noise/selection regenerate in-scan
            H = jnp.stack([rngstream.stream_base_key(
                seed, tr, rngstream.FADING_TAG) for tr in range(trials)])
            Z = jnp.stack([rngstream.stream_base_key(
                seed, tr, rngstream.NOISE_TAG) for tr in range(trials)])
            SEL = jnp.stack([rngstream.stream_base_key(
                seed, tr, rngstream.SELECT_TAG) for tr in range(trials)])
        else:
            H = np.stack([sample_fading_batch(self.dep.lambdas,
                                              seed * 1000 + tr, T)
                          for tr in range(trials)])           # (trials, T, N)
            if jagg.needs_noise:
                Z = np.stack([rngstream.trial_rng(seed, tr)
                              .standard_normal((T, d))
                              for tr in range(trials)])
            else:
                Z = np.zeros((trials, T, 1))
            if jagg.sel_stream_np is not None:
                SEL = np.stack([jagg.sel_stream_np(seed, tr, T)
                                for tr in range(trials)])     # (trials, T, S)
            else:
                SEL = np.zeros((trials, T, 1))
        keys = jnp.stack([rngstream.dither_base_key(seed, tr)
                          for tr in range(trials)])
        bkeys = jnp.stack([rngstream.batch_base_key(seed, tr)
                           for tr in range(trials)])
        # fault-, participation- and arrival-stream base keys ride along
        # unconditionally (cheap, and keeps trial_fn's arity mode-,
        # fault-, participation- and async-blind); when the matching
        # layer is disabled the traced program never consumes them
        fkeys = jnp.stack([rngstream.fault_base_key(seed, tr)
                           for tr in range(trials)])
        pkeys = jnp.stack([rngstream.participate_base_key(seed, tr)
                           for tr in range(trials)])
        akeys = jnp.stack([rngstream.arrival_base_key(seed, tr)
                           for tr in range(trials)])

        runner = self._get_runner(jagg, trials, n_seg, eval_every, rng)
        # host arrays, not device arrays: the jitted scan places each one
        # where it runs (with shard_trials, straight onto every chip
        # instead of staging a copy on the first)
        f32 = np.float32
        w0 = np.asarray(self.task.init_params(), f32)
        eta = np.asarray(self.eta, f32)
        radius = np.asarray(
            np.inf if self.project_radius is None else self.project_radius,
            f32)
        lat_div = np.asarray(
            self.dep.cfg.bandwidth_hz if jagg.is_ota else 1.0, f32)
        budget = np.asarray(
            np.inf if time_budget_s is None else time_budget_s, f32)
        Ts = np.arange(T, dtype=np.int32).reshape(n_seg, eval_every)
        if rng == "fast":
            A, B_, C = H, Z, SEL          # per-trial base keys as-is
        else:
            # the host replay stacks enter the scan rounded to f32
            seg = lambda a, dt: np.asarray(a.reshape(
                (trials, n_seg, eval_every) + a.shape[2:]), dt)
            A, B_, C = seg(H, np.complex64), seg(Z, f32), seg(SEL, f32)
        args = (w0, eta, radius, lat_div, budget, self.xs, self.ys,
                keys, bkeys, fkeys, pkeys, akeys, A, B_, C, Ts)
        return jagg, runner, args

    def _evaluate(self, ws):
        """Global loss + test accuracy at every eval point, vmapped over
        (trials * E) model states in the trainer's float32 eval precision."""
        trials, E, d = ws.shape
        wf = ws.reshape(trials * E, d).astype(jnp.float32)
        losses = self._loss_v(wf, self.x_all, self.y_all)
        accs = self._acc_v(wf, self.x_test, self.y_test)
        return (np.asarray(losses).reshape(trials, E),
                np.asarray(accs).reshape(trials, E))
