#!/usr/bin/env python3
"""Smoke test of the FL engine's main path on a TPU, checked against oracles.

    python3 chip_smoke.py                # one chip: the phases below
    python3 chip_smoke.py --four-chips   # four chips: sharded trials only

Phases, in one process and in this order; each prints one line that
names it:

  fig2_ota      Fig. 2 OTA-FL at paper width (N=50, d=7850, 1000 samples
                per device), cut to 20 rounds, 2 trials and one step size:
                ideal, proposed and vanilla OTA through
                ``repro.api.execute``, each against the NumPy oracle on the
                same seeds (``repro.fl.parity`` tolerances); the oracle
                runs on the host CPU.
  fig2_digital  Fig. 2 digital FL (N=10): proposed digital and best
                channel-norm, the same way.
  bias_layers   Fig. 2 width with client sampling (S=16), dropout 0.1
                zero-filled and buffered async (K=4), against the oracle.
  payload       a digital uplink at N=256, d=10^6 with 8-bit codes through
                ``FLEngine`` on the fused pack path; one round's fused sum
                against ``kernels.ref.quantized_weighted_sum_ref``.
  design        the batched f64 design solvers against the SciPy oracle,
                against the same solves on the host CPU (native f64), and
                for the feasibility of the designs they return.

Every engine phase also counts the Pallas kernels (``tpu_custom_call``) in
its scans' programs, and there must be some in each scan with an uplink. With
``--four-chips`` the script runs only ``sharded_trials``: Fig. 2 OTA at
paper width with 4 trials sharded over 4 chips
(``FLEngine(shard_trials=True)``) against the same trials on one chip.

The script exits non-zero before any phase when JAX finds no TPU, and on
any failed check. Its last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
import types
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro.api import SweepSpec, execute, schemes  # noqa: E402
from repro.api import materialize as mat  # noqa: E402
from repro.api.scenarios import fig2_digital_sc, fig2_ota_sc  # noqa: E402
from repro.core import baselines as B  # noqa: E402
from repro.core import digital_design, ota_design, rngstream  # noqa: E402
from repro.core.async_fl import AsyncSpec  # noqa: E402
from repro.core.bounds import ObjectiveWeights  # noqa: E402
from repro.core.channel import WirelessConfig, make_deployment  # noqa: E402
from repro.core.faults import FaultSpec  # noqa: E402
from repro.core.sca_jax import ORACLE_RTOL  # noqa: E402
from repro.data.loader import FLDataset  # noqa: E402
from repro.fl.engine import FLEngine  # noqa: E402
from repro.fl.parity import parity_violations  # noqa: E402
from repro.fl.tasks import SyntheticHighDimTask  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402

#: The chip's design objectives against the same solves on the host CPU.
#: A TPU emulates f64 as a pair of f32 (about 48 mantissa bits): on a v5e
#: the two solves end 1.9e-10 (OTA) and 3.9e-10 (digital) apart.
HOST_F64_RTOL = 1e-9


@dataclasses.dataclass(frozen=True)
class Sizes:
    """How far each phase is cut. ``FULL`` is what the script runs; the
    tests rehearse the same phases on the CPU at a tiny size."""

    quick: bool = False             # fig2_*_sc(quick=...)
    ota_devices: int = 50           # fig2 OTA width (paper: 50)
    digital_devices: int = 10       # fig2 digital width (paper: 10)
    rounds: int = 20
    trials: int = 2
    eval_every: int = 10
    kappa: float | None = None      # None: estimate on the data
    clients_per_round: int = 16
    payload_devices: int = 256
    payload_dim: int = 10 ** 6
    payload_rounds: int = 3
    design_devices: int = 50


FULL = Sizes()


_COMPILE_S = [0.0]


def _count_compile(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += duration


def _kernels(engine, aggregator, **kw) -> int:
    """``tpu_custom_call`` ops in the scan of ``engine.run(aggregator,
    **kw)``, counted in its lowered program (nothing is compiled)."""
    _, runner, args = engine.prepare(aggregator, **kw)
    return runner.lower(*args).as_text().count("tpu_custom_call")


def _scan_kernels(spec) -> dict:
    """Kernels in the engine scan of each of ``spec``'s schemes.

    ``execute`` keeps its engines to itself, so they are built again here
    the way it builds them, with each design family solved by the batched
    solver as ``execute`` solves it."""
    ctx = mat.materialize(spec)
    for family, batch in (("ota", ota_design.design_ota_batch),
                          ("digital", digital_design.design_digital_batch)):
        if family in schemes.design_families(spec.schemes):
            params, objs = batch([ctx.design_spec(family)])
            ctx.set_design(family, "designed", params[0], objs[0])
    r = spec.run
    counts = {}
    for key in schemes.expand_schemes(spec.schemes):
        agg = schemes.build_scheme(key, ctx)
        eng = FLEngine(ctx.task, ctx.ds, ctx.dep, r.etas[0] * ctx.eta_max,
                       batch_size=r.batch_size, payload_dtype=r.payload_dtype,
                       fault=spec.fault,
                       clients_per_round=r.clients_per_round,
                       participation=r.participation,
                       participation_probs=ctx.participation_probs(agg),
                       mode=r.mode, async_spec=spec.async_,
                       async_weights=ctx.async_weights(agg))
        counts[key] = _kernels(eng, agg, rounds=r.rounds, trials=r.trials,
                               eval_every=r.eval_every, seed=r.seed)
    return counts


def _as_log(rec, quantized: bool):
    """A result record (means over trials) in ``TrainLog`` form."""
    return types.SimpleNamespace(
        scheme=rec["scheme"], rounds=np.asarray(rec["rounds"]),
        global_loss=np.asarray(rec["loss_mean"])[None],
        accuracy=np.asarray(rec["acc_mean"])[None],
        wall_time_s=np.asarray(rec["wall_time_s"]), opt_error=None,
        quantized=quantized)


def _max_rel(got, want) -> float:
    # ideal FedAvg spends no airtime: its wall-clock is 0 in both logs
    return float(np.max(np.abs(np.subtract(got, want))
                        / np.maximum(np.abs(want), 1e-30)))


def _engine_vs_oracle(spec, on_chip: bool, must_learn=(),
                      quantized: bool = False) -> dict:
    """Run ``spec`` through ``execute`` on the engine and on the NumPy
    oracle (one sweep over ``run.backend``: both cells share the dataset,
    kappa and the batched design solve; the oracle's task programs run on
    the host CPU) and compare the trajectories. ``quantized``: the
    schemes have a digital uplink. The schemes in ``must_learn`` must also
    bring the loss down."""
    sweep = SweepSpec(name=spec.name, base=spec,
                      axes={"run.backend": ("jax", "numpy")})
    rs = execute(sweep, save=False, jobs=1)
    cells = {c.overrides["run.backend"]: c.payload["logs"] for c in rs}
    n_test = spec.data.n_test_per_class * spec.task.n_classes
    out, problems = {}, []
    for eng, ora in zip(cells["jax"], cells["numpy"]):
        key = eng["scheme_key"]
        bad = parity_violations(_as_log(ora, quantized),
                                _as_log(eng, quantized), n_test)
        loss = eng["loss_mean"]
        falls = loss[-1] < loss[0]
        out[key] = {"loss": [loss[0], loss[-1]],
                    "acc": eng["acc_mean"][-1],
                    "oracle_loss": ora["loss_mean"][-1],
                    "max_rel_loss_diff": _max_rel(loss, ora["loss_mean"]),
                    "max_rel_wall_diff": _max_rel(eng["wall_time_s"][1:],
                                                  ora["wall_time_s"][1:])}
        problems += [f"{key}: {b}" for b in bad]
        if not falls and key in must_learn:
            problems.append(f"{key}: loss did not fall ({loss[0]:.4g} -> "
                            f"{loss[-1]:.4g})")
    counts = _scan_kernels(spec)
    # ideal FedAvg averages exact gradients: no uplink, so no kernel
    bare = [k for k, n in counts.items() if not n and k != "ideal"]
    if on_chip and bare:
        problems.append(f"compiled scans without a kernel: {bare}")
    return {"ok": not problems, "schemes": out, "tpu_custom_call": counts,
            "problems": problems}



def _fig2_ota(sz: Sizes):
    spec = fig2_ota_sc(quick=sz.quick, n_devices=sz.ota_devices)
    run = dataclasses.replace(spec.run, rounds=sz.rounds, trials=sz.trials,
                              eval_every=sz.eval_every, etas=(0.25,))
    return spec.replace(run=run, design=dataclasses.replace(
        spec.design, kappa=sz.kappa))


def phase_fig2_ota(sz: Sizes, on_chip: bool) -> dict:
    spec = _fig2_ota(sz).replace(
        schemes=("ideal", "proposed_ota", "vanilla_ota"))
    # Vanilla OTA-FL inverts the weakest channel, so the AWGN it amplifies
    # swamps the update and its loss rises (as in the paper's Fig. 2)
    return _engine_vs_oracle(spec, on_chip,
                             must_learn=("ideal", "proposed_ota"))


def phase_fig2_digital(sz: Sizes, on_chip: bool) -> dict:
    spec = fig2_digital_sc(quick=sz.quick, n_devices=sz.digital_devices)
    run = dataclasses.replace(spec.run, rounds=sz.rounds, trials=sz.trials,
                              eval_every=sz.eval_every, etas=(1.0,))
    spec = spec.replace(
        run=run, schemes=("proposed_digital", "best_channel_norm"),
        design=dataclasses.replace(spec.design, kappa=sz.kappa))
    return _engine_vs_oracle(spec, on_chip, must_learn=spec.schemes,
                             quantized=True)


def phase_bias_layers(sz: Sizes, on_chip: bool) -> dict:
    spec = _fig2_ota(sz)
    spec = spec.replace(
        schemes=("proposed_ota",),
        run=dataclasses.replace(spec.run,
                                clients_per_round=sz.clients_per_round,
                                mode="async"),
        fault=FaultSpec(dropout_prob=0.1, on_missing="zero"),
        async_=AsyncSpec(buffer_rounds=4))
    return _engine_vs_oracle(spec, on_chip)


def phase_payload(sz: Sizes, on_chip: bool) -> dict:
    n, d = sz.payload_devices, sz.payload_dim
    task = SyntheticHighDimTask(d, seed=0)
    xs, ys = task.device_data(n)
    ds = FLDataset.from_shards([(xs[m], ys[m]) for m in range(n)],
                               xs[0], ys[0])
    dep = make_deployment(WirelessConfig(n_devices=n, seed=1))
    cfg = dep.cfg
    # every device uploads 8-bit codes: r_max = 8 and d >= FUSED_MIN_DIM
    # put the uplink on the fused quantize -> pack -> accumulate path
    agg = B.BestChannel(dep, d, task.g_max, cfg.energy_per_symbol,
                        cfg.noise_power, cfg.bandwidth_hz, k=n, r_bits=8)
    eng = FLEngine(task, ds, dep, eta=0.5)
    kw = dict(rounds=sz.payload_rounds, trials=1,
              eval_every=sz.payload_rounds, seed=0)
    log = eng.run(agg, **kw)
    count = _kernels(eng, agg, **kw)
    problems = []
    if not np.all(np.isfinite(log.global_loss)):
        problems.append("non-finite loss")
    if on_chip and not count:
        problems.append("the compiled scan holds no kernel")

    # one round's operands: the fused kernels against the sequential jnp
    # reference, both on the default device
    g = task.device_grads_fn(jnp.zeros(d, jnp.float32), jnp.asarray(xs),
                             jnp.asarray(ys))
    u = rngstream.dither_block(rngstream.dither_base_key(0, 0), 0, n, d)
    levels = jnp.full(n, 255.0, jnp.float32)
    wts = jnp.full(n, 1.0 / n, jnp.float32)
    fused = jax.jit(lambda g, u: ops.quantized_weighted_sum(
        g, levels, u, wts, r_max=8))(g, u)
    m = jnp.max(jnp.abs(g), axis=1)
    want = jax.jit(ref.quantized_weighted_sum_ref)(g, m, levels, u, wts)
    err = np.asarray(jnp.abs(fused - want))
    # where the kernel and XLA round (g + m)/step a last ulp apart on a
    # stochastic-rounding boundary, one device's code moves by one step:
    # a coordinate then differs by that device's weighted grid step
    m = np.asarray(m, np.float64)
    step_lo = float(2 * m.min() / 255 / n)
    step_hi = float(2 * m.max() / 255 / n)
    flips = err > 0.5 * step_lo
    fp = float(err[~flips].max()) if np.any(~flips) else 0.0
    scale = float(np.abs(np.asarray(want)).max())
    if fp > 1e-5 * scale:
        problems.append(f"fused vs reference: {fp:.3g} beyond f32 "
                        f"round-off of {scale:.3g}")
    if flips.sum() > 1e-4 * d or err.max() > 2.01 * step_hi:
        problems.append(f"fused vs reference: {int(flips.sum())} "
                        f"coordinates off by up to {err.max():.3g}")
    stats = jax.devices()[0].memory_stats() or {}
    return {"ok": not problems, "n_devices": n, "dim": d,
            "fused_path": d >= ops.FUSED_MIN_DIM,
            "loss": [float(log.global_loss[0, 0]),
                     float(log.global_loss[0, -1])],
            "fused_vs_ref": {"max_abs_err": float(err.max()),
                             "fp_err": fp, "boundary_flips":
                             int(flips.sum()), "step": step_hi},
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "tpu_custom_call": {agg.name: count}, "problems": problems}


def phase_design(sz: Sizes, on_chip: bool) -> dict:
    n = sz.design_devices
    dep = make_deployment(WirelessConfig(n_devices=n, seed=1))
    cfg = dep.cfg
    base = ObjectiveWeights.strongly_convex(eta=0.5, mu=0.01, kappa_sc=3.0,
                                            n=n)
    # two corners of tests/test_design_batch.py's (omega_var, omega_bias)
    # grid: the SciPy oracle takes about a minute per point at N=50
    grid = [ObjectiveWeights(omega_var=base.omega_var * a,
                             omega_bias=base.omega_bias * a)
            for a in (0.3, 3.0)]
    ota_specs = [ota_design.OTADesignSpec(
        lambdas=dep.lambdas, dim=7850, g_max=20.0,
        e_s=cfg.energy_per_symbol, n0=cfg.noise_power, weights=w)
        for w in grid]
    dig_specs = [digital_design.DigitalDesignSpec(
        lambdas=dep.lambdas, dim=7850, g_max=20.0,
        e_s=cfg.energy_per_symbol, n0=cfg.noise_power,
        bandwidth_hz=cfg.bandwidth_hz, t_max_s=0.2, weights=w)
        for w in grid]
    out, problems = {}, []
    for family, specs, batch, oracle, iters, infeasible in (
            ("ota", ota_specs, ota_design.design_ota_batch,
             ota_design.design_ota_sca, 6, _ota_infeasible),
            ("digital", dig_specs, digital_design.design_digital_batch,
             digital_design.design_digital_sca, 4, _digital_infeasible)):
        params, objs = batch(specs)
        # the witness: the same solves on the host CPU, in native f64
        with jax.default_device(jax.devices("cpu")[0]):
            _, host_objs = batch(specs)
        gap = float(np.max(np.abs(objs - host_objs) / np.abs(host_objs)))
        ratios = []
        for spec, f in zip(specs, objs):
            _, res = oracle(spec, n_iters=iters)
            ratios.append(float(f / res.objective))
        bad = [msg for spec, p, f in zip(specs, params, objs)
               for msg in infeasible(spec, p, f)]
        out[family] = {"objective_over_scipy": ratios,
                       "objective_vs_host_f64": gap}
        # one-sided, as in tests/test_design_batch.py: the batched solver
        # may find a better point than SciPy's SCA, never a worse one
        if not all(np.isfinite(ratios)) or max(ratios) > 1 + ORACLE_RTOL:
            problems.append(f"{family}: batched objective beyond "
                            f"{ORACLE_RTOL:g} of SciPy: {ratios}")
        if not gap <= HOST_F64_RTOL:
            problems.append(f"{family}: objective {gap:.3g} from the host "
                            f"f64 solve, beyond {HOST_F64_RTOL:g}")
        problems += [f"{family}: {msg}" for msg in bad]
    return {"ok": not problems, "n_devices": n, **out,
            "problems": problems}


def _ota_infeasible(spec, params, objective) -> list:
    """Where an OTA design breaks (15)'s constraints, or its objective is
    not (15a) at the returned design (``tests/test_design_batch.py``)."""
    pl = params.participation_levels(spec.lambdas)
    bad = []
    if np.any(pl < 0) or np.any(pl > 1) or abs(pl.sum() - 1) > 1e-9:
        bad.append(f"participation levels off the simplex (sum {pl.sum()})")
    if np.any(params.gammas > spec.gamma_max() * (1 + 1e-12)):
        bad.append("gamma above its power limit")
    f = ota_design.true_objective_from_gamma(spec, params.gammas)
    if abs(f - objective) > 1e-9 * abs(f):
        bad.append(f"objective {objective} is not (15a) at the design: {f}")
    return bad


def _digital_infeasible(spec, params, objective) -> list:
    """Where a digital design breaks (17)'s constraints: the latency budget,
    the bit range, the participation simplex."""
    pl = params.participation_levels(spec.lambdas)
    lat = params.expected_latency(spec.lambdas)
    bad = []
    if abs(pl.sum() - 1) > 1e-6:
        bad.append(f"participation levels sum to {pl.sum()}")
    if np.any(params.r_bits < 1) or np.any(params.r_bits > spec.r_max):
        bad.append(f"bits outside [1, {spec.r_max}]")
    if lat > spec.t_max_s * (1 + 1e-9):
        bad.append(f"expected latency {lat} over the budget {spec.t_max_s}")
    return bad


def phase_sharded_trials(sz: Sizes, on_chip: bool) -> dict:
    """Fig. 2 OTA, trials sharded over every visible chip, against the
    same trials on chip 0."""
    n_chips = len(jax.devices())
    spec = _fig2_ota(sz)
    spec = spec.replace(run=dataclasses.replace(spec.run, trials=n_chips))
    ctx = mat.materialize(spec)
    params, objs = ota_design.design_ota_batch([ctx.design_spec("ota")])
    ctx.set_design("ota", "designed", params[0], objs[0])
    agg = schemes.build_scheme("proposed_ota", ctx)
    eta = spec.run.etas[0] * ctx.eta_max
    kw = dict(rounds=spec.run.rounds, trials=n_chips,
              eval_every=spec.run.eval_every, seed=spec.run.seed)
    sharded = FLEngine(ctx.task, ctx.ds, ctx.dep, eta,
                       shard_trials=True).run(agg, **kw)
    stats = [dev.memory_stats() or {} for dev in jax.devices()]
    peaks = [s.get("peak_bytes_in_use") for s in stats]
    in_use = [s.get("bytes_in_use") for s in stats]
    single = FLEngine(ctx.task, ctx.ds, ctx.dep, eta).run(agg, **kw)
    n_test = spec.data.n_test_per_class * spec.task.n_classes
    problems = parity_violations(single, sharded, n_test)
    if on_chip and None not in peaks and min(peaks) < 0.5 * max(peaks):
        problems.append(f"peak bytes uneven across chips: {peaks}")
    return {"ok": not problems, "chips": n_chips,
            "loss_sharded": sharded.global_loss[:, -1].tolist(),
            "loss_one_chip": single.global_loss[:, -1].tolist(),
            "peak_bytes_in_use": peaks, "bytes_in_use": in_use,
            "problems": problems}


PHASES = (("fig2_ota", phase_fig2_ota),
          ("fig2_digital", phase_fig2_digital),
          ("bias_layers", phase_bias_layers),
          ("payload", phase_payload),
          ("design", phase_design))


def run_phase(name, fn, sz: Sizes, on_chip: bool) -> dict:
    """One phase with its wall and backend-compile seconds; an exception
    is a failed phase, not a crash of the script."""
    t0, c0 = time.perf_counter(), _COMPILE_S[0]
    try:
        res = fn(sz, on_chip)
    except Exception as e:  # reported on the phase line, then exit 1
        traceback.print_exc()
        res = {"ok": False, "problems": [f"{type(e).__name__}: {e}"]}
    res["seconds"] = round(time.perf_counter() - t0, 1)
    res["compile_seconds"] = round(_COMPILE_S[0] - c0, 1)
    print(f"{name}: {json.dumps(res, default=float)}", flush=True)
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the trials-sharded phase on 4 chips")
    args = ap.parse_args(argv)
    devices = jax.devices()
    platform = devices[0].platform
    want = 4 if args.four_chips else 1
    if platform != "tpu" or len(devices) < want:
        print(f"chip_smoke: needs {want} TPU chip(s); JAX finds "
              f"{len(devices)} {platform} device(s). Nothing was run.",
              file=sys.stderr)
        return 2
    print(f"compile cache: {compile_cache.enable()}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(_count_compile)
    phases = ((("sharded_trials", phase_sharded_trials),)
              if args.four_chips else PHASES)
    failed = [name for name, fn in phases
              if not run_phase(name, fn, FULL, on_chip=True)["ok"]]
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
