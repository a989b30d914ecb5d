"""FL simulation loop (eq. (2)/(13)): broadcast -> local grads -> wireless
aggregation -> projected SGD step, with Monte-Carlo trials over fading/noise.

Matches Sec. V's protocol:
  * fixed device deployment (fixed {Lambda_m}) across trials,
  * independent fading + PS noise per trial,
  * full-batch local gradients (|B| = |D|, sigma_m = 0) by default, or SGD
    mini-batches via ``batch_size`` (counter-based index draws shared
    bit-for-bit with the JAX engine),
  * projection onto the ball W = {||w|| <= D/2} in the strongly convex case,
  * per-round latency accounting (OTA: d/B; digital: realized TDMA time),
  * optional wireless fault injection (``core.faults``): dropouts, erasures,
    deep fades and stragglers drawn from the counter-based FAULT stream
    (bit-shared with the JAX engine), with graceful-degradation policies
    applied to the gradients before the aggregation scheme runs,
  * optional partial participation (``core.participation``): Bernoulli
    client sampling with static inclusion probabilities drawn from the
    counter-based PARTICIPATE stream (bit-shared with the JAX engine),
    payloads scaled by the uniform inverse propensity N/S,
  * optional buffered-async aggregation (``core.async_fl``,
    ``mode="async"``): per-device delivery/staleness events drawn from the
    counter-based ARRIVAL stream (bit-shared with the JAX engine) against
    precomputed rate/CDF tables; the PS consumes staleness-discounted
    payloads from a last-K gradient buffer, missing devices zero-fill or
    replay their last delivered payload.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np
import jax

from ..core import async_fl
from ..core import participation as participation_lib
from ..core import rngstream
from ..core.baselines import Aggregator
from ..core.channel import Deployment, FadingProcess
from ..core.faults import FaultSpec, fault_masks, survival_prob


@dataclasses.dataclass
class TrainLog:
    scheme: str
    rounds: np.ndarray          # (T_eval,)
    wall_time_s: np.ndarray     # cumulative uplink latency at eval points
    global_loss: np.ndarray     # (trials, T_eval)
    accuracy: np.ndarray        # (trials, T_eval)
    opt_error: Optional[np.ndarray] = None   # ||w_t - w*||^2 if w* known
    quantized: bool = False     # digital uplink: gradients were quantized

    def mean_std(self, field: str):
        v = getattr(self, field)
        return v.mean(axis=0), v.std(axis=0)

    def final_accuracy(self) -> float:
        return float(self.accuracy[:, -1].mean())


class FLTrainer:
    def __init__(self, task, dataset, deployment: Deployment,
                 eta: float, *, project_radius: Optional[float] = None,
                 batch_size: Optional[int] = None,
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
        self.batch_size = batch_size
        self.payload_dtype = payload_dtype
        # a disabled FaultSpec normalizes to None so fault-free runs take
        # the exact pre-fault code path (bit-identical trajectories) and
        # hit the same engine cache entry as a no-fault trainer
        self.fault = fault if fault is not None and fault.enabled else None
        # same normalization for client sampling: clients_per_round=None
        # -> None (strict no-op); otherwise the shared validated config
        # (core.participation) both backends consume bit-for-bit. The
        # loss/datasize policies derive their capped-simplex weights from
        # (task, dataset) — pure NumPy, identical bits on both backends.
        part_weights = None
        if (clients_per_round is not None and participation_probs is None
                and participation in participation_lib.WEIGHTED_POLICIES):
            part_weights = participation_lib.policy_weights(
                participation, task, dataset)
        self.participation = participation_lib.resolve(
            clients_per_round, participation, participation_probs,
            n_devices=deployment.n_devices, lambdas=deployment.lambdas,
            weights=part_weights)
        # mode="sync" normalizes the async layer to None (strict no-op);
        # otherwise the resolved tables (core.async_fl) are shared with
        # the JAX engine bit-for-bit
        self.async_ = async_fl.resolve(mode, async_spec,
                                       deployment.n_devices, async_weights)
        self._mode = mode
        self._async_spec = async_spec
        self._async_weights = async_weights
        self._engine = None
        # stack device data once whenever sizes allow: (N, n, feat). The
        # stacked view serves the full-batch path AND the counter-based
        # mini-batch fast path (task.device_grads_at on a (N, B) index
        # block); unequal-sized devices fall back to per-device gathers.
        if len({len(d) for d in dataset.devices}) == 1:
            self.xs = np.stack([d.x for d in dataset.devices])
            self.ys = np.stack([d.y for d in dataset.devices])
        else:
            if batch_size is None:
                raise ValueError(
                    "full-batch training needs equal-sized device datasets "
                    "(stacked (N, n, feat) gradients); set batch_size")
            self.xs = self.ys = None

    def _project(self, w: np.ndarray) -> np.ndarray:
        if self.project_radius is None:
            return w
        nrm = np.linalg.norm(w)
        if nrm <= self.project_radius:
            return w
        return w * (self.project_radius / nrm)

    def run(self, aggregator: Aggregator, *, rounds: int, trials: int = 3,
            eval_every: int = 10, seed: int = 0,
            w_star: Optional[np.ndarray] = None,
            time_budget_s: Optional[float] = None,
            backend: str = "auto", rng: str = "replay") -> TrainLog:
        """Run the Monte-Carlo FL protocol.

        backend: "numpy" — reference Python-loop path; "jax" — vectorized
        vmap/scan engine (``fl.engine``), errors if the scheme has no JAX
        port; "auto" (default) — the engine whenever the scheme is
        registered in its port routing table (all 14 paper baselines are),
        NumPy otherwise. Mini-batching, time budgets and unequal-sized
        device datasets run natively in the engine — including the mixed
        full/mini-batch regime (batch_size >= some |D_m|), where full
        devices take weighted full-data gradients and mini devices the
        counter-based draw: batch indices are counter-based
        (``core.rngstream``, ragged per-device rows when sizes differ) and
        the budget-freeze mask is evaluated in-scan, so both backends
        replay the same random streams and trajectories agree to ~1e-5
        (tests/test_engine_parity.py; mixed rounds to ~1e-4 — the weighted
        sum reorders the oracle's mean reduction).

        rng: "replay" (default) — byte-compatible with the NumPy oracle's
        sequential streams (fading/AWGN/selection precomputed per trial);
        "fast" — every stream is counter-based threefry generated inside
        the scan, zero host-side per-trial precompute and O(N*d) memory.
        Fast draws come from the same laws but a different stream:
        statistically equivalent to replay, not bit-equal. Engine-only —
        errors on the NumPy path.
        """
        if backend not in ("auto", "jax", "numpy"):
            raise ValueError(f"unknown backend {backend!r}")
        if rng not in ("replay", "fast"):
            raise ValueError(f"rng must be 'replay' or 'fast', got {rng!r}")
        if backend == "numpy" and rng == "fast":
            raise ValueError(
                "rng='fast' runs only on the JAX engine; the NumPy backend "
                "is the replay oracle by definition")
        if backend == "numpy" and self.payload_dtype != "f32":
            raise ValueError(
                "payload_dtype='bf16' runs only on the JAX engine (the "
                "mixed-precision uplink cast lives in its scan); the NumPy "
                "backend is the f32/f64 replay oracle by definition")
        if backend != "numpy":
            from .engine import FLEngine, as_functional
            supported = as_functional(aggregator) is not None
            if supported:
                if self.xs is not None:
                    # normalized like FLEngine (batch_size >= |D_m| is full
                    # batch) so the degenerate case still reuses the cache
                    bs = FLEngine.effective_batch_size(self.batch_size,
                                                       self.xs.shape[1])
                else:
                    bs = self.batch_size
                if (self._engine is None
                        or self._engine.eta != self.eta
                        or self._engine.project_radius != self.project_radius
                        or self._engine.batch_size != bs
                        or self._engine.payload_dtype != self.payload_dtype
                        or self._engine.fault != self.fault
                        or self._engine.participation != self.participation
                        or self._engine.async_ != self.async_):
                    part = self.participation
                    self._engine = FLEngine(
                        self.task, self.ds, self.dep, self.eta,
                        project_radius=self.project_radius,
                        batch_size=bs, payload_dtype=self.payload_dtype,
                        fault=self.fault,
                        clients_per_round=(part.clients if part else None),
                        participation=(part.policy if part else "uniform"),
                        participation_probs=(part.probs_array()
                                             if part else None),
                        mode=self._mode, async_spec=self._async_spec,
                        async_weights=self._async_weights)
                return self._engine.run(aggregator, rounds=rounds,
                                        trials=trials, eval_every=eval_every,
                                        seed=seed, w_star=w_star,
                                        time_budget_s=time_budget_s,
                                        rng=rng)
            if backend == "jax":
                raise ValueError(
                    f"backend='jax' unsupported here: scheme "
                    f"{type(aggregator).__name__} has no JAX port")
        if rng == "fast":
            raise ValueError(
                "rng='fast' needs the JAX engine, but this run dispatches "
                f"to the NumPy path (scheme {type(aggregator).__name__})")
        if self.payload_dtype != "f32":
            raise ValueError(
                "payload_dtype='bf16' needs the JAX engine, but this run "
                "dispatches to the NumPy path (scheme "
                f"{type(aggregator).__name__})")
        # the oracle's task programs run on the host CPU, so that no
        # accelerator numerics reach the reference
        with jax.default_device(jax.devices("cpu")[0]):
            return self._run_oracle(aggregator, rounds=rounds, trials=trials,
                                    eval_every=eval_every, seed=seed,
                                    w_star=w_star,
                                    time_budget_s=time_budget_s)

    def _run_oracle(self, aggregator: Aggregator, *, rounds, trials,
                    eval_every, seed, w_star, time_budget_s) -> TrainLog:
        """The NumPy reference loop of :meth:`run`."""
        eval_rounds = list(range(0, rounds + 1, eval_every))
        losses = np.zeros((trials, len(eval_rounds)))
        accs = np.zeros((trials, len(eval_rounds)))
        opt_err = (np.zeros((trials, len(eval_rounds)))
                   if w_star is not None else None)
        wall = np.zeros((trials, len(eval_rounds)))
        x_all = np.concatenate([d.x for d in self.ds.devices])
        y_all = np.concatenate([d.y for d in self.ds.devices])
        # fault layer (counter-based FAULT stream, shared bit-for-bit with
        # the JAX engine); q/deadline are static per-run quantities
        fault = self.fault
        if fault is not None:
            q_surv = survival_prob(fault, self.dep.lambdas)
            straggler_mult = float(fault.straggler_mult)
            deadline = fault.deadline_s
        # client sampling (counter-based PARTICIPATE stream, shared
        # bit-for-bit with the JAX engine); probabilities are static
        part = self.participation
        # (inclusion probabilities rounded to f32 like the engine's, so
        # both backends compare the f32 draws against the same values)
        if part is not None:
            part_probs = rngstream.f32_table(part.probs_array())
            part_scale = float(part.scale)
        # buffered-async layer (counter-based ARRIVAL stream, shared
        # bit-for-bit with the JAX engine); the rate/CDF tables are
        # rounded to f32 like the engine's, so the in-loop realization is
        # exact comparisons/gathers only
        asy = self.async_
        if asy is not None:
            a_rates = rngstream.f32_table(asy.rates_array())
            a_cdf = rngstream.f32_table(asy.cdf_array())
            a_disc = asy.discounts_array()
            a_pscale = asy.payload_scale_array()

        for trial in range(trials):
            rng = np.random.default_rng((seed, trial, 17))
            fading = FadingProcess(self.dep, seed=seed * 1000 + trial)
            if fault is not None and fault.on_missing == "stale":
                g_stale = np.zeros((self.dep.n_devices, self.task.dim))
            if asy is not None:
                # pre-start buffer slots are zeros: staleness draws that
                # reach past round 0 deliver nothing
                a_buf = np.zeros((asy.buffer_rounds, self.dep.n_devices,
                                  self.task.dim))
                if asy.on_missing == "stale":
                    g_alast = np.zeros((self.dep.n_devices, self.task.dim))
            w = self.task.init_params()
            t_wall, ei = 0.0, 0
            for t in range(rounds + 1):
                if t in eval_rounds:
                    losses[trial, ei] = self.task.global_loss(w, x_all, y_all)
                    accs[trial, ei] = self.task.accuracy(
                        w, self.ds.x_test, self.ds.y_test)
                    if opt_err is not None:
                        opt_err[trial, ei] = float(np.sum((w - w_star) ** 2))
                    wall[trial, ei] = t_wall
                    ei += 1
                if t == rounds or (time_budget_s is not None
                                   and t_wall >= time_budget_s):
                    # budget hit / horizon reached: freeze remaining evals
                    # at the last *written* eval. The t=0 eval always runs
                    # before the first budget check, so ei >= 1 here and
                    # slot ei-1 is never stale/unwritten.
                    assert ei > 0, "freeze before any eval was written"
                    last = ei - 1
                    for j in range(ei, len(eval_rounds)):
                        losses[trial, j] = losses[trial, last]
                        accs[trial, j] = accs[trial, last]
                        wall[trial, j] = t_wall
                        if opt_err is not None:
                            opt_err[trial, j] = opt_err[trial, last]
                    break
                # mini-batch indices are counter-based (threefry on
                # (seed, trial, t, m), core.rngstream) so the JAX engine
                # regenerates bit-identical batches in-scan, and the
                # sequential trial rng stays reserved for AWGN/selection
                if self.batch_size is None:
                    grads = self.task.device_grads(w, self.xs, self.ys)
                elif (self.xs is not None
                      and self.batch_size < self.xs.shape[1]):
                    idx = rngstream.batch_block_np(
                        seed, trial, t, self.dep.n_devices,
                        self.xs.shape[1], self.batch_size)
                    grads = self.task.device_grads_at(w, self.xs, self.ys,
                                                      idx)
                elif self.xs is not None:
                    # batch_size >= |D_m|: full batch, no draw consumed
                    grads = self.task.device_grads(w, self.xs, self.ys)
                else:
                    bx, by = [], []
                    for m, d in enumerate(self.ds.devices):
                        ind = (rngstream.batch_indices_np(
                                   seed, trial, t, m, len(d),
                                   self.batch_size)
                               if self.batch_size < len(d) else None)
                        x_b, y_b = d.batch(self.batch_size, indices=ind)
                        bx.append(x_b)
                        by.append(y_b)
                    if len({b.shape[0] for b in bx}) == 1:
                        grads = self.task.device_grads(w, np.stack(bx),
                                                       np.stack(by))
                    else:
                        # mixed full/mini regime (batch_size >= some |D_m|):
                        # batches can't stack, so take per-device gradients
                        grads = np.stack(
                            [self.task.device_grads(w, x_b[None],
                                                    y_b[None])[0]
                             for x_b, y_b in zip(bx, by)])
                h = fading.sample(t)
                # client sampling: Bernoulli cohort + uniform inverse
                # propensity N/S, applied BEFORE the fault layer (same
                # ordering as the engine scan: payload cast ->
                # participation -> fault policy -> dither)
                if part is not None:
                    up = rngstream.participation_block_np(
                        seed, trial, t, self.dep.n_devices)
                    chi = up < part_probs
                    grads = grads * (chi.astype(np.float64)
                                     * part_scale)[:, None]
                # buffered-async delivery: the last-K buffer shifts and
                # each device delivers a staleness-discounted payload (or
                # nothing), upstream of the fault layer and the scheme —
                # the same ordering as the engine scan (payload cast ->
                # participation -> async delivery -> fault -> dither)
                if asy is not None:
                    ua = rngstream.arrival_block_np(
                        seed, trial, t, self.dep.n_devices)
                    grads, ok_a, a_buf = async_fl.async_round(
                        grads, a_buf, ua, a_rates, a_cdf, a_disc, a_pscale)
                    if asy.on_missing == "stale":
                        grads, g_alast = async_fl.stale_replace(
                            grads, ok_a, g_alast)
                    else:
                        grads = grads * ok_a.astype(np.float64)[:, None]
                # graceful degradation: transform the gradients BEFORE the
                # aggregation scheme sees them (same ordering as the engine
                # scan: payload cast -> fault policy -> dither), so every
                # scheme inherits the policy without per-scheme code
                if fault is not None:
                    uf = rngstream.fault_block_np(seed, trial, t,
                                                  self.dep.n_devices)
                    okb, straggler = fault_masks(uf, np.abs(h), fault)
                    if fault.on_missing == "zero":
                        grads = grads * okb.astype(np.float64)[:, None]
                    elif fault.on_missing == "reweight":
                        grads = grads * (okb.astype(np.float64)
                                         / q_surv)[:, None]
                    else:
                        # stale: replay the last received gradient — the
                        # single last-gradient code path shared with the
                        # async buffer (core.async_fl)
                        grads, g_stale = async_fl.stale_replace(
                            grads, okb, g_stale)
                # digital schemes consume counter-based dither (one (N, d)
                # block per round, bit-replayable by the JAX engine); OTA
                # schemes only draw AWGN from the sequential trial rng
                # the kwarg is only passed when a block exists, so custom
                # OTA aggregators with the pre-dither 4-arg round() keep
                # working
                if aggregator.is_ota:
                    res = aggregator.round(list(grads), h, t, rng)
                else:
                    u_t = rngstream.dither_block_np(seed, trial, t,
                                                    self.dep.n_devices,
                                                    self.task.dim)
                    res = aggregator.round(list(grads), h, t, rng,
                                           dither=u_t)
                lat_s = (res.latency_s / self.dep.cfg.bandwidth_hz
                         if aggregator.is_ota else res.latency_s)
                if fault is not None:
                    # delivering stragglers stretch the round; a deadline
                    # instead caps it (stragglers then count as missing,
                    # see core.faults.fault_masks)
                    if bool(np.any(straggler & okb)):
                        lat_s = lat_s * straggler_mult
                    if deadline is not None:
                        lat_s = min(lat_s, float(deadline))
                t_wall += lat_s
                w = self._project(w - self.eta * res.ghat)
        return TrainLog(scheme=aggregator.name,
                        rounds=np.asarray(eval_rounds, dtype=np.int64),
                        wall_time_s=wall.mean(axis=0), global_loss=losses,
                        accuracy=accs, opt_error=opt_err,
                        quantized=not aggregator.is_ota)


def solve_w_star(task, x_all: np.ndarray, y_all: np.ndarray,
                 iters: int = 4000, eta: Optional[float] = None) -> np.ndarray:
    """Reference minimizer w* of the (strongly convex) global objective via
    full-batch GD to high precision."""
    w = task.init_params()
    eta = eta if eta is not None else 2.0 / (task.mu + task.smooth_l)
    xs = x_all[None]
    ys = y_all[None]
    for _ in range(iters):
        g = task.device_grads(w, xs, ys)[0]
        w = w - eta * g
    return w
