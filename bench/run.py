#!/usr/bin/env python3
"""Chip benchmark of the FL engine: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix. The run builds the program's objects for them (set-up), warms
up with one call, then runs a closed loop of Monte-Carlo calls for
``--seconds`` (the window), each call ``FLEngine.run`` with a fresh seed.
After the window it reads the device's peak memory (the larger of the
allocator's peak and the compiled scan's own footprint), recomputes a
sample of the calls with the plain reference, and prints one JSON line:
the cell's end-to-end metrics with ``--trace 0``, its per-layer metrics,
read from a profiler trace of the window, with ``--trace 1``.

The run needs as many TPU chips as the cell asks for; with fewer, or on
another platform, it exits 2 and prints no result. JAX's persistent
compilation cache is the program's (``repro.compile_cache``): the
checkout's ``.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` is set.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CALL_SPAN = "bench_call:"


class Compiles:
    """Counts the programs JAX compiles or loads from its cache."""

    def __init__(self):
        self.compiled = 0          # XLA backend compilations
        self.loaded = 0            # persistent-cache hits
        self.compile_s = 0.0

    def on_duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1
            self.compile_s += duration

    def on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.loaded += 1

    @property
    def programs(self) -> int:
        return self.compiled + self.loaded


def require_chips(jax, chips: int) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: the cell needs {chips} TPU chip(s); JAX finds "
              f"{len(devices)} {devices[0].platform} device(s). Nothing "
              "was run.", file=sys.stderr)
        raise SystemExit(2)
    return devices[:chips]


def check(readings: dict, limits: dict) -> dict:
    """``{number: {"value", "limit"}}`` for each number the cell compares."""
    return {k: {"value": readings[k], "limit": v["limit"]}
            for k, v in limits["compare"].items()}


def passed(checks: dict) -> bool:
    # NaN compares false: a missing or non-finite reading fails
    return all(c["value"] <= c["limit"] for c in checks.values())


def per_layer(reg, cell_entry, ctx) -> dict:
    out = {}
    for m in reg.per_layer(cell_entry):
        value = reg.module("metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(args, reg) -> dict:
    import jax
    from repro import compile_cache

    from bench import fl, trace as tr, window
    from bench.metrics import Context

    cell_entry = reg.cell(args.workload)
    devices = require_chips(jax, cell_entry["chips"])
    cache_dir = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = Compiles()
    jax.monitoring.register_event_duration_secs_listener(
        compiles.on_duration)
    jax.monitoring.register_event_listener(compiles.on_event)
    split = {"import_s": time.perf_counter() - T_START}

    config = reg.config(cell_entry["config"])
    traffic = reg.traffic(cell_entry["traffic"])
    limits = reg.limits(args.workload)
    cfgmod = reg.module("configs", cell_entry["config"])
    t = time.perf_counter()
    data = cfgmod.dataset(config)
    split["dataset_s"] = time.perf_counter() - t
    t = time.perf_counter()
    engine, aggregator, kw = cfgmod.program(config, traffic, data)
    split["program_s"] = time.perf_counter() - t
    cell = fl.Cell(engine, aggregator, kw, args.seed,
                   keep=limits["calls_checked"])
    t, c0 = time.perf_counter(), compiles.compile_s
    cell.run(fl.call_seed(args.seed, -1))
    split["warmup_s"] = time.perf_counter() - t
    split["compile_s"] = compiles.compile_s - c0
    split["programs_compiled"] = compiles.compiled
    split["programs_from_cache"] = compiles.loaded
    setup_s = time.perf_counter() - T_START
    print("setup: " + " ".join(f"{k}={v:.6g}" for k, v in split.items())
          + f" cache={cache_dir}", file=sys.stderr, flush=True)

    log_dir = None
    if args.trace:
        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(log_dir)

    def timed(i):
        with jax.profiler.TraceAnnotation(f"{CALL_SPAN}{i}"):
            cell.call(i)

    before = compiles.programs
    calls = window.closed_loop(timed if args.trace else cell.call,
                               args.seconds)
    in_window = compiles.programs - before
    if args.trace:
        jax.profiler.stop_trace()
    cell.settle()                    # the last kept call's states, on the host
    print(f"window: calls={len(calls)} seconds="
          f"{window.window_seconds(calls):.6f} programs_compiled_or_loaded="
          f"{in_window}", file=sys.stderr, flush=True)
    secs = sorted(c.seconds for c in calls)
    print(f"calls: min_s={secs[0]:.6f} median_s={secs[len(secs) // 2]:.6f} "
          f"max_s={secs[-1]:.6f} over_1.25x_median="
          f"{sum(s > 1.25 * secs[len(secs) // 2] for s in secs)}",
          file=sys.stderr, flush=True)

    stats = [d.memory_stats() or {} for d in devices]
    in_use = max(s.get("peak_bytes_in_use", 0) for s in stats)
    program = cell.program_bytes()
    print(f"memory: peak_bytes_in_use={in_use} scan_program_bytes={program}",
          file=sys.stderr, flush=True)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": max(in_use, program)}

    result = {"correct": False, "attempted": len(calls), "failed": 0}
    if args.trace:
        trace = tr.load(tr.find_xplane(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
        ctx = Context(trace=trace, span_prefix=CALL_SPAN, cell=cell,
                      config_module=cfgmod, config=config, traffic=traffic,
                      peaks=reg.peaks(device["kind"]), registry=reg)
        result["metrics"] = per_layer(reg, cell_entry, ctx)
        device["busy_s"] = ctx.busy_s
        device["window_s"] = ctx.window_s
        result["breakdown"] = ctx.breakdown()
    else:
        result["metrics"] = {
            "device_rounds_per_s": {
                "value": window.rate(calls, cell.device_rounds_per_call),
                "unit": "device-rounds/s"},
            "call_p90_s": {"value": window.percentile(
                [c.seconds for c in calls], 90), "unit": "s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    result["device"] = device

    # the check: the sampled calls against the plain reference
    kept = cell.kept
    del cell, engine, aggregator
    ref_mod = reg.module("configs", cell_entry["config"] + "_ref")
    reference = ref_mod.Reference(config, traffic)
    worst: dict = {}
    for index, seed, got in kept:
        r = fl.readings(got, reference.run(seed))
        bad = not passed(check(r, limits))
        result["failed"] += int(bad)
        print(f"check: call={index} seed={seed} " + " ".join(
            f"{k}={v:.6g}" for k, v in r.items()), file=sys.stderr)
        for k, v in r.items():       # the largest; a NaN stays
            prev = worst.get(k)
            if prev is None or v != v or (prev == prev and v > prev):
                worst[k] = v
    checks = check(worst, limits)
    result["correct"] = bool(kept) and passed(checks)
    result["checks"] = checks
    for k, c in checks.items():
        print(f"{k} {c['value']:.6g} limit {c['limit']:.6g}",
              file=sys.stderr)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench.registry import Registry
    result = run(args, Registry())
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
