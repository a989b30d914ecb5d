"""NumPy-vs-JAX FL engine wall-clock benchmark (ROADMAP north-star check).

Runs the same Monte-Carlo FL workload through both ``FLTrainer`` backends —
the Python-loop NumPy reference and the vmap/scan JAX engine (Pallas
epilogue kernels, interpret mode on CPU) — and reports wall-clock plus the
steady-state speedup, for the OTA schemes AND the digital selection suite
(top-K / bit-allocation schemes run as jittable ops since the full-coverage
port). Both backends replay identical random streams, so the max trajectory
deviation is recorded as a built-in parity check.

    PYTHONPATH=src python -m benchmarks.engine_bench [--smoke] [--minibatch]

Writes experiments/results/engine_bench.json.

``--minibatch`` benchmarks the SGD regime (counter-based batch indices
regenerated in-scan) plus a time-budgeted run — the two options that used
to force the NumPy fallback. Writes
experiments/results/engine_bench_minibatch.json.

``--digital-long`` runs the 1500-round digital horizon through the engine
alone and records wall-clock + peak RSS — the O(N*d) streaming-dither
memory proof (the retired (trials, T, N, d) dither tensor would add
trials*T*N*d*8 bytes on top). ``--rss-budget-mb`` turns it into a CI guard
(exit 1 on budget overrun; used by scripts/verify.sh). Writes
experiments/results/engine_bench_digital.json.

``--scale`` runs the ``rng="fast"`` population-scale grid (N up to 1024
devices at the fig2 model dimension, zero host-side RNG precompute) plus
the fig2-sized replay-vs-fast speedup record; honors ``--rss-budget-mb``
and writes the schema-stamped perf trajectory to the repo-root
``BENCH_engine_scale.json`` (tracked across PRs, unlike the
experiments/results artifacts).
"""
from __future__ import annotations

import argparse
import resource
import sys
import time

import numpy as np

from .common import (design_digital, design_ota, dump_json, make_sc_setup,
                     result_payload, save_result)
from repro.core import baselines as B
from repro.fl.trainer import FLTrainer
from repro import compile_cache


def _time_backend(trainer, agg, backend, *, rounds, trials, eval_every,
                  seed, repeats=1, rng="replay"):
    best, log = np.inf, None
    for _ in range(repeats):
        t0 = time.perf_counter()
        log = trainer.run(agg, rounds=rounds, trials=trials,
                          eval_every=eval_every, seed=seed, backend=backend,
                          rng=rng)
        best = min(best, time.perf_counter() - t0)
    return best, log


def _time_suite(trainer, suite, *, trials, eval_every, seed=5,
                row_prefix="engine_bench", extra=None):
    """Time every (key, aggregator, rounds) suite entry through both
    backends (numpy / jax cold / jax warm) with the built-in trajectory
    parity check; returns the harness CSV rows and the JSON result dicts.
    ``extra`` merges additional fields (e.g. batch_size) into each dict."""
    # warm the task's jitted grad/loss functions once so the NumPy timing
    # measures the backend, not shared first-call compilation
    trainer.run(suite[0][1], rounds=2, trials=1, eval_every=1, seed=1,
                backend="numpy")
    task, dep = trainer.task, trainer.dep
    rows, results = [], []
    for key, agg, t_rounds in suite:
        t_np, log_np = _time_backend(trainer, agg, "numpy", rounds=t_rounds,
                                     trials=trials, eval_every=eval_every,
                                     seed=seed)
        t_cold, _ = _time_backend(trainer, agg, "jax", rounds=t_rounds,
                                  trials=trials, eval_every=eval_every,
                                  seed=seed)
        t_warm, log_jx = _time_backend(trainer, agg, "jax", rounds=t_rounds,
                                       trials=trials, eval_every=eval_every,
                                       seed=seed, repeats=2)
        dev = float(np.max(np.abs(log_np.global_loss - log_jx.global_loss)))
        res = {
            "scheme": agg.name, "rounds": t_rounds, "trials": trials,
            "n_devices": dep.n_devices, "dim": task.dim,
            "numpy_s": t_np, "jax_cold_s": t_cold, "jax_warm_s": t_warm,
            "speedup_warm": t_np / t_warm, "speedup_cold": t_np / t_cold,
            "max_loss_deviation": dev,
            **(extra or {}),
        }
        results.append(res)
        rows.append((f"{row_prefix}/{key}",
                     t_warm * 1e6 / max(t_rounds * trials, 1),
                     f"speedup={res['speedup_warm']:.1f}x;parity={dev:.1e}"))
    return rows, results


def run(quick: bool = True, *, n_devices: int = 20, trials: int = 3,
        rounds: int = 200, samples_per_device: int = 1000,
        result_name: str = "engine_bench"):
    """Benchmark entry (also wired into benchmarks.run).

    Defaults are a fig2-sized run: N=20 devices, 3 Monte-Carlo trials, 200
    rounds on the strongly convex softmax task at the paper protocol's
    1000 samples/device (``make_sc_setup`` default). ``quick`` keeps that;
    full mode doubles the horizon.
    """
    if not quick:
        rounds *= 2
    eval_every = max(rounds // 20, 1) * 2
    task, ds, dep, eta_max = make_sc_setup(
        n_devices, samples_per_device=samples_per_device,
        n_train_per_class=max((n_devices * samples_per_device) // 10, 200))
    eta = 0.25 * eta_max
    params, _ = design_ota(task, dep, eta)
    dig_params, _ = design_digital(task, dep, eta)
    trainer = FLTrainer(task, ds, dep, eta=eta)

    cfg = dep.cfg
    wargs = (task.dim, task.g_max, cfg.energy_per_symbol, cfg.noise_power)
    # NumPy quantize loop dominates; keep the digital horizons laptop-sized.
    # Snap to the eval grid: the engine only simulates rounds up to the last
    # eval point, so a non-multiple horizon would bill the NumPy backend for
    # rounds the engine never runs and inflate the speedup.
    dig_rounds = max((rounds // 4 // eval_every) * eval_every, eval_every)
    suite = [
        ("proposed_ota", B.ProposedOTA(params), rounds),
        ("vanilla_ota", B.VanillaOTA(*wargs), rounds),
        ("opc_ota_fl", B.OPCOTAFL(*wargs), rounds),
        ("bbfl_alternative", B.BBFLAlternative(dep, *wargs), rounds),
        ("proposed_digital", B.ProposedDigital(dig_params), dig_rounds),
        ("best_channel", B.BestChannel(dep, *wargs, cfg.bandwidth_hz),
         dig_rounds),
        ("uqos", B.UQOS(dep, *wargs, cfg.bandwidth_hz), dig_rounds),
        ("fedtoe", B.FedTOE(dep, *wargs, cfg.bandwidth_hz), dig_rounds),
    ]
    rows, results = _time_suite(trainer, suite, trials=trials,
                                eval_every=eval_every)
    payload = result_payload("engine_bench", quick=quick, results=results)
    save_result(result_name, payload)
    return rows, payload


def run_minibatch(quick: bool = True, *, n_devices: int = 20, trials: int = 3,
                  rounds: int = 200, batch_size: int = 64,
                  samples_per_device: int = 1000,
                  result_name: str = "engine_bench_minibatch"):
    """Mini-batch (SGD) engine-vs-NumPy benchmark.

    Stochastic device gradients are the regime the engine used to punt to
    the NumPy oracle; since the counter-based batch-sampler port it runs
    in-scan ((N, B) index blocks regenerated per round from a scan-carried
    threefry key, gathered through the task's device_grads_at path).
    Records the wall-clock gap and the built-in trajectory-parity check,
    plus one time-budgeted engine run exercising the in-scan freeze mask.
    Writes experiments/results/engine_bench_minibatch.json.
    """
    if not quick:
        rounds *= 2
    eval_every = max(rounds // 20, 1) * 2
    task, ds, dep, eta_max = make_sc_setup(
        n_devices, samples_per_device=samples_per_device,
        n_train_per_class=max((n_devices * samples_per_device) // 10, 200))
    eta = 0.25 * eta_max
    params, _ = design_ota(task, dep, eta)
    dig_params, _ = design_digital(task, dep, eta)
    trainer = FLTrainer(task, ds, dep, eta=eta,
                        batch_size=min(batch_size, samples_per_device))

    cfg = dep.cfg
    wargs = (task.dim, task.g_max, cfg.energy_per_symbol, cfg.noise_power)
    dig_rounds = max((rounds // 4 // eval_every) * eval_every, eval_every)
    suite = [
        ("proposed_ota", B.ProposedOTA(params), rounds),
        ("vanilla_ota", B.VanillaOTA(*wargs), rounds),
        ("proposed_digital", B.ProposedDigital(dig_params), dig_rounds),
        ("best_channel", B.BestChannel(dep, *wargs, cfg.bandwidth_hz),
         dig_rounds),
    ]
    rows, results = _time_suite(trainer, suite, trials=trials,
                                eval_every=eval_every,
                                row_prefix="engine_bench_minibatch",
                                extra={"batch_size": trainer.batch_size})
    # in-scan time-budget path: freeze after ~60% of the horizon's airtime
    agg = suite[1][1]
    budget = 0.6 * rounds * task.dim / cfg.bandwidth_hz
    t0 = time.perf_counter()
    log_b = trainer.run(agg, rounds=rounds, trials=trials,
                        eval_every=eval_every, seed=5,
                        time_budget_s=budget, backend="jax")
    t_budget = time.perf_counter() - t0
    payload = result_payload(
        "engine_bench_minibatch", quick=quick,
        batch_size=trainer.batch_size, results=results,
        time_budget_run={
            "scheme": agg.name, "rounds": rounds, "trials": trials,
            "time_budget_s": budget, "jax_s": t_budget,
            "frozen_wall_s": float(np.asarray(log_b.wall_time_s)[-1]),
        })
    save_result(result_name, payload)
    return rows, payload


def run_digital_long(*, rounds: int = 1500, trials: int = 1,
                     n_devices: int = 20, eval_every: int = 100):
    """1500-round digital horizon, engine-only, with the peak-RSS record.

    The engine streams dither from scan-carried keys (O(N*d) per round);
    this run is infeasible at the old materialized-dither design, whose
    (trials, T, N, d) tensor alone would add ``dither_tensor_mb`` on top of
    the measured peak.
    """
    task, ds, dep, eta_max = make_sc_setup(
        n_devices, samples_per_device=1000,
        n_train_per_class=max(n_devices * 100, 200))
    eta = 0.25 * eta_max
    dig_params, _ = design_digital(task, dep, eta)
    trainer = FLTrainer(task, ds, dep, eta=eta)
    results = []
    for key, agg in (("proposed_digital", B.ProposedDigital(dig_params)),
                     ("fedtoe", B.FedTOE(dep, task.dim, task.g_max,
                                         dep.cfg.energy_per_symbol,
                                         dep.cfg.noise_power,
                                         dep.cfg.bandwidth_hz))):
        t0 = time.perf_counter()
        log = trainer.run(agg, rounds=rounds, trials=trials,
                          eval_every=eval_every, seed=5, backend="jax")
        elapsed = time.perf_counter() - t0
        results.append({
            "scheme": agg.name, "key": key, "rounds": rounds,
            "trials": trials, "n_devices": n_devices, "dim": task.dim,
            "jax_s": elapsed,
            "rounds_per_s": rounds * trials / elapsed,
            "final_loss": float(log.global_loss[:, -1].mean()),
            "final_acc": float(log.accuracy[:, -1].mean()),
        })
    peak_rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
    dither_tensor_mb = trials * rounds * n_devices * task.dim * 8 / 2 ** 20
    payload = result_payload(
        "engine_bench_digital", results=results, peak_rss_mb=peak_rss_mb,
        retired_dither_tensor_mb=dither_tensor_mb,
        streamed_dither_mb_per_round=n_devices * task.dim * 4 / 2 ** 20)
    save_result("engine_bench_digital", payload)
    return payload


def run_scale(quick: bool = True, *, n_grid=None, rounds: int = 30,
              trials: int = 1, samples_per_device: int = 50,
              fig2_rounds: int = 200, fig2_trials: int = 8,
              rss_budget_mb=None):
    """Population-scale fast-RNG benchmark -> top-level BENCH_engine_scale.json.

    Two measurements behind the ``rng="fast"`` mode (counter-based
    threefry streams generated in-scan, zero host-side per-trial
    precompute):

    1. **Scale grid** — N up to 1024 devices at the fig2 model dimension
       (d = 7850) through the engine in fast mode, with the cumulative
       peak-RSS record. Replay mode would precompute a (trials, T, d)
       AWGN block plus a (trials, T, N) fading tensor per run
       (``replay_host_mb`` records what each point dodges); fast mode
       carries three (2,)-uint32 keys per trial. Non-designed OTA
       schemes (VanillaOTA / OPC-OTA-FL) so the grid never waits on an
       N=1024 design solve nor on the interpret-mode quantize kernel.
       A population-scale partial-participation cell (N=2000 devices,
       expected cohort S=64 via ``core.participation``) rides along as
       ``participation_scale`` — the scenario the 2 GB RSS guard covers.
    2. **fig2-scale replay-vs-fast** — the same fig2-sized workload
       (N=20, d=7850) end-to-end in both modes; the recorded
       ``speedup_fast`` is the perf trajectory tracked across PRs. On
       CPU the scan dominates this horizon, so the honest number here is
       modest — the scaling win is the grid above, where replay's host
       tensors would grow with trials*T*(d+N) and fast mode's stay O(1).

    The payload is schema-stamped (``result_payload``) and written to the
    repo root — not ``experiments/results`` — so the perf trajectory is
    versioned next to the code. ``rss_budget_mb`` is recorded in the
    payload; ``main()`` enforces it (exit 1 on overrun — the
    scripts/verify.sh CI guard).
    """
    from pathlib import Path

    if n_grid is None:
        n_grid = (256, 1024) if quick else (128, 256, 512, 1024)
    if quick:
        fig2_rounds, fig2_trials = min(fig2_rounds, 120), min(fig2_trials, 6)
    eval_every = max(rounds // 2, 1)
    scale_results = []
    for n_devices in n_grid:
        task, ds, dep, eta_max = make_sc_setup(
            n_devices, samples_per_device=samples_per_device,
            n_train_per_class=max((n_devices * samples_per_device) // 10,
                                  200))
        eta = 0.25 * eta_max
        cfg = dep.cfg
        wargs = (task.dim, task.g_max, cfg.energy_per_symbol,
                 cfg.noise_power)
        trainer = FLTrainer(task, ds, dep, eta=eta)
        for key, agg in (("vanilla_ota", B.VanillaOTA(*wargs)),
                         ("opc_ota_fl", B.OPCOTAFL(*wargs))):
            t_cold, _ = _time_backend(trainer, agg, "jax", rounds=rounds,
                                      trials=trials, eval_every=eval_every,
                                      seed=5, rng="fast")
            t_warm, log = _time_backend(trainer, agg, "jax", rounds=rounds,
                                        trials=trials, eval_every=eval_every,
                                        seed=5, rng="fast")
            peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
            scale_results.append({
                "scheme": agg.name, "key": key, "n_devices": n_devices,
                "dim": task.dim, "rounds": rounds, "trials": trials,
                "jax_cold_s": t_cold, "jax_warm_s": t_warm,
                "rounds_per_s": rounds * trials / t_warm,
                "final_loss": float(log.global_loss[:, -1].mean()),
                "peak_rss_mb": peak,
                # what replay mode would have materialized host-side for
                # this run: (trials, T, d) float64 AWGN + (trials, T, N)
                # complex128 fading
                "replay_host_mb": trials * rounds *
                    (task.dim * 8 + n_devices * 16) / 2 ** 20,
            })
        del trainer, task, ds, dep

    # population-scale partial participation: N=2000 devices, an expected
    # cohort of S=64 per round (core.participation), fast counter streams
    # — the cell the 2 GB RSS guard covers. The participation mask is a
    # trace-time-static (N,) Bernoulli draw + scale inside the scan, so
    # its memory footprint stays O(N) regardless of rounds/trials.
    part_n, part_s = 2000, 64
    task, ds, dep, eta_max = make_sc_setup(
        part_n, samples_per_device=20,
        n_train_per_class=max((part_n * 20) // 10, 200))
    cfg = dep.cfg
    agg = B.VanillaOTA(task.dim, task.g_max, cfg.energy_per_symbol,
                       cfg.noise_power)
    trainer = FLTrainer(task, ds, dep, eta=0.25 * eta_max,
                        clients_per_round=part_s)
    t_cold, _ = _time_backend(trainer, agg, "jax", rounds=rounds,
                              trials=trials, eval_every=eval_every,
                              seed=5, rng="fast")
    t_warm, log = _time_backend(trainer, agg, "jax", rounds=rounds,
                                trials=trials, eval_every=eval_every,
                                seed=5, rng="fast")
    participation_scale = {
        "scheme": agg.name, "key": "vanilla_ota",
        "n_devices": part_n, "clients_per_round": part_s,
        "participation": "uniform", "dim": task.dim,
        "samples_per_device": 20, "rounds": rounds, "trials": trials,
        "jax_cold_s": t_cold, "jax_warm_s": t_warm,
        "rounds_per_s": rounds * trials / t_warm,
        "final_loss": float(log.global_loss[:, -1].mean()),
        "peak_rss_mb":
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0,
    }
    del trainer, task, ds, dep

    # fig2-scale end-to-end: replay's per-trial host precompute + transfer
    # vs fast's in-scan streams, same scheme, same horizon
    task, ds, dep, eta_max = make_sc_setup(20, samples_per_device=1000,
                                           n_train_per_class=2000)
    cfg = dep.cfg
    agg = B.VanillaOTA(task.dim, task.g_max, cfg.energy_per_symbol,
                       cfg.noise_power)
    trainer = FLTrainer(task, ds, dep, eta=0.25 * eta_max)
    fig2_eval = max(fig2_rounds // 10, 1)
    t_replay, _ = _time_backend(trainer, agg, "jax", rounds=fig2_rounds,
                                trials=fig2_trials, eval_every=fig2_eval,
                                seed=5, repeats=3, rng="replay")
    t_fast, _ = _time_backend(trainer, agg, "jax", rounds=fig2_rounds,
                              trials=fig2_trials, eval_every=fig2_eval,
                              seed=5, repeats=3, rng="fast")
    peak_rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
    payload = result_payload(
        "engine_bench_scale", quick=quick,
        scale={"samples_per_device": samples_per_device,
               "n_grid": list(n_grid), "results": scale_results},
        participation_scale=participation_scale,
        fig2_speedup={
            "scheme": agg.name, "n_devices": 20, "dim": task.dim,
            "rounds": fig2_rounds, "trials": fig2_trials,
            "replay_warm_s": t_replay, "fast_warm_s": t_fast,
            "speedup_fast": t_replay / t_fast,
            "replay_host_mb": fig2_trials * fig2_rounds *
                (task.dim * 8 + 20 * 16) / 2 ** 20,
        },
        peak_rss_mb=peak_rss_mb, rss_budget_mb=rss_budget_mb)
    out = Path(__file__).resolve().parents[1] / "BENCH_engine_scale.json"
    out.write_text(dump_json(payload))
    rows = [(f"engine_bench_scale/N{r['n_devices']}/{r['key']}",
             r["jax_warm_s"] * 1e6 / max(rounds * trials, 1),
             f"rps={r['rounds_per_s']:.0f};rss={r['peak_rss_mb']:.0f}MB")
            for r in scale_results]
    ps = participation_scale
    rows.append((f"engine_bench_scale/N{ps['n_devices']}"
                 f"_S{ps['clients_per_round']}/participation",
                 ps["jax_warm_s"] * 1e6 / max(rounds * trials, 1),
                 f"rps={ps['rounds_per_s']:.0f};"
                 f"rss={ps['peak_rss_mb']:.0f}MB"))
    return rows, payload


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI (N=10, 2 trials, 40 rounds)")
    ap.add_argument("--minibatch", action="store_true",
                    help="SGD mini-batch suite (engine in-scan batch "
                         "sampling vs the NumPy oracle loop)")
    ap.add_argument("--digital-long", action="store_true",
                    help="1500-round digital engine run + peak-RSS record")
    ap.add_argument("--scale", action="store_true",
                    help="population-scale fast-RNG grid (N up to 1024 at "
                         "fig2 d) + fig2 replay-vs-fast speedup; writes "
                         "top-level BENCH_engine_scale.json")
    ap.add_argument("--rss-budget-mb", type=float, default=None,
                    help="with --digital-long/--scale: exit 1 if peak RSS "
                         "exceeds")
    args = ap.parse_args()
    compile_cache.enable()
    if args.scale:
        if args.smoke:
            rows, payload = run_scale(
                quick=True, n_grid=(1024,), rounds=20, trials=1,
                fig2_rounds=120, fig2_trials=6,
                rss_budget_mb=args.rss_budget_mb)
        else:
            rows, payload = run_scale(quick=False,
                                      rss_budget_mb=args.rss_budget_mb)
        for r in payload["scale"]["results"]:
            print(f"N={r['n_devices']} {r['key']}: {r['rounds']}x"
                  f"{r['trials']} rounds in {r['jax_warm_s']:.2f}s warm "
                  f"({r['rounds_per_s']:.0f} rounds/s, "
                  f"RSS {r['peak_rss_mb']:.0f} MB)")
        ps = payload["participation_scale"]
        print(f"N={ps['n_devices']} S={ps['clients_per_round']} "
              f"partial participation ({ps['key']}): {ps['rounds']}x"
              f"{ps['trials']} rounds in {ps['jax_warm_s']:.2f}s warm "
              f"({ps['rounds_per_s']:.0f} rounds/s, "
              f"RSS {ps['peak_rss_mb']:.0f} MB)")
        f2 = payload["fig2_speedup"]
        print(f"fig2-scale ({f2['scheme']}, {f2['rounds']}x{f2['trials']}): "
              f"replay {f2['replay_warm_s']:.2f}s vs fast "
              f"{f2['fast_warm_s']:.2f}s -> {f2['speedup_fast']:.2f}x")
        print(f"peak RSS {payload['peak_rss_mb']:.0f} MB "
              f"-> BENCH_engine_scale.json")
        if (args.rss_budget_mb is not None
                and payload["peak_rss_mb"] > args.rss_budget_mb):
            print(f"FAIL: peak RSS exceeds budget "
                  f"{args.rss_budget_mb:.0f} MB — is a replay tensor "
                  "materialized in fast mode?", file=sys.stderr)
            sys.exit(1)
        return
    if args.digital_long:
        payload = run_digital_long()
        for r in payload["results"]:
            print(f"{r['key']}: {r['rounds']}x{r['trials']} rounds in "
                  f"{r['jax_s']:.1f}s ({r['rounds_per_s']:.0f} rounds/s)")
        print(f"peak RSS {payload['peak_rss_mb']:.0f} MB (retired dither "
              f"tensor alone: {payload['retired_dither_tensor_mb']:.0f} MB)")
        if (args.rss_budget_mb is not None
                and payload["peak_rss_mb"] > args.rss_budget_mb):
            print(f"FAIL: peak RSS exceeds budget {args.rss_budget_mb:.0f} MB"
                  " — is the dither replay materialized again?",
                  file=sys.stderr)
            sys.exit(1)
        return
    if args.minibatch:
        # smoke records separately so CI never clobbers the fig2-sized
        # artifacts
        if args.smoke:
            rows, payload = run_minibatch(
                quick=True, n_devices=10, trials=2, rounds=40,
                batch_size=32, samples_per_device=100,
                result_name="engine_bench_minibatch_smoke")
        else:
            rows, payload = run_minibatch(quick=True)
    elif args.smoke:
        rows, payload = run(quick=True, n_devices=10, trials=2, rounds=40,
                            samples_per_device=100,
                            result_name="engine_bench_smoke")
    else:
        rows, payload = run(quick=True)
    print("scheme,backend=numpy[s],jax_cold[s],jax_warm[s],speedup,parity")
    for r in payload["results"]:
        print(f"{r['scheme']},{r['numpy_s']:.3f},{r['jax_cold_s']:.3f},"
              f"{r['jax_warm_s']:.3f},{r['speedup_warm']:.1f}x,"
              f"{r['max_loss_deviation']:.1e}")
    worst = min(r["speedup_warm"] for r in payload["results"][:2])
    print(f"min OTA steady-state speedup: {worst:.1f}x")
    if args.minibatch:
        tb = payload["time_budget_run"]
        print(f"time-budget run ({tb['scheme']}): froze at "
              f"{tb['frozen_wall_s']:.3f}s of {tb['time_budget_s']:.3f}s "
              f"budget in {tb['jax_s']:.2f}s wall")


if __name__ == "__main__":
    main()
