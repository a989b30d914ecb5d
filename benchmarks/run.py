"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Full payloads are saved to
experiments/results/*.json.

Usage: PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME]
       PYTHONPATH=src python -m benchmarks.run --list

``--only`` accepts an exact suite name or a name prefix (``--only fig2``
runs both fig2 suites); unknown names print the registry instead of a
KeyError. A suite that raises prints an ``ERROR`` row; the others still
run, and the command then exits 1.
"""
from __future__ import annotations

import argparse
import sys
import time
import types

from repro import compile_cache


def _registry() -> dict:
    from . import (fig2_ota_sc, fig2_digital_sc, fig3_nonconvex,
                   kernel_bench, theorem_validation, engine_bench,
                   design_bench, sweep_snr_het, sweep_fault,
                   sweep_participation, sweep_async)
    return {
        "kernel_bench": kernel_bench,
        "theorem_validation": theorem_validation,
        "engine_bench": engine_bench,
        # the SGD mini-batch + time-budget engine suite shares the module
        # but runs as its own harness entry
        "engine_bench_minibatch": types.SimpleNamespace(
            run=engine_bench.run_minibatch,
            **{"__doc__": engine_bench.run_minibatch.__doc__}),
        # fast-RNG population-scale grid + fig2 replay-vs-fast record
        # (writes the top-level BENCH_engine_scale.json perf trajectory)
        "engine_bench_scale": types.SimpleNamespace(
            run=engine_bench.run_scale,
            **{"__doc__": engine_bench.run_scale.__doc__}),
        # payload-scale fused quantize->pack->aggregate pipeline
        # (writes the top-level BENCH_kernel_payload.json record)
        "kernel_bench_payload": types.SimpleNamespace(
            run=kernel_bench.run_payload,
            **{"__doc__": kernel_bench.run_payload.__doc__}),
        "design_bench": design_bench,
        "fig2_ota_sc": fig2_ota_sc,
        "fig2_digital_sc": fig2_digital_sc,
        "fig3_nonconvex": fig3_nonconvex,
        "sweep_snr_het": sweep_snr_het,
        "sweep_fault": sweep_fault,
        "sweep_participation": sweep_participation,
        "sweep_async": sweep_async,
    }


def _print_registry(modules: dict, stream=sys.stdout) -> None:
    print("registered benchmark suites:", file=stream)
    for name, mod in modules.items():
        doc = (getattr(mod, "__doc__", None)
               or getattr(getattr(mod, "run", None), "__doc__", None) or "")
        first = doc.strip().splitlines()[0] if doc.strip() else ""
        print(f"  {name:24s} {first}", file=stream)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale rounds/trials (slow)")
    ap.add_argument("--only", default=None,
                    help="run a single benchmark, or all matching a "
                         "name prefix")
    ap.add_argument("--list", action="store_true",
                    help="list registered suites and exit")
    args = ap.parse_args()
    compile_cache.enable()
    quick = not args.full

    modules = _registry()
    if args.list:
        _print_registry(modules)
        return
    if args.only:
        selected = ({args.only: modules[args.only]} if args.only in modules
                    else {k: v for k, v in modules.items()
                          if k.startswith(args.only)})
        if not selected:
            print(f"unknown benchmark {args.only!r} (no name or prefix "
                  "match)", file=sys.stderr)
            _print_registry(modules, stream=sys.stderr)
            sys.exit(2)
        modules = selected

    print("name,us_per_call,derived")
    failed = []
    for name, mod in modules.items():
        t0 = time.time()
        try:
            rows, _ = mod.run(quick=quick)
        except Exception as e:
            # report and go on to the next suite, but the run fails
            print(f"{name},0,ERROR:{type(e).__name__}:{e}", flush=True)
            failed.append(name)
            continue
        for r in rows:
            print(f"{r[0]},{r[1]:.1f},{r[2]}", flush=True)
        print(f"{name}/TOTAL,{(time.time() - t0) * 1e6:.0f},ok", flush=True)
    if failed:
        print(f"{len(failed)} suite(s) failed: {', '.join(failed)}",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
