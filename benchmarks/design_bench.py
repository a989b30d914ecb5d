"""Design-solver benchmark: per-point SciPy SCA vs batched JAX (Sec. IV).

The Sec.-IV bias-variance design (problems (15)/(17)) used to be the
slowest stage of every figure pipeline: each ``design_*_sca`` call runs
the SCA outer loop as a Python loop of SLSQP solves, and the paper's
sweeps multiply that by dozens of independent grid points. This benchmark
times both solvers on an (omega_bias, omega_var) trade-off grid around
the fig2 operating point and records objective parity — the JAX path must
match the SciPy SCA oracle to 1e-3 relative (or beat it) on every point.

    PYTHONPATH=src python -m benchmarks.design_bench            # fig2-sized
    PYTHONPATH=src python -m benchmarks.design_bench --smoke    # CI guard

Default (fig2-sized: N=50, 4x4 grid per family) writes
experiments/results/design_bench.json; ``--smoke`` runs a small grid,
writes design_bench_smoke.json, and exits 1 if the JAX path loses to the
oracle anywhere (used by scripts/verify.sh).
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from .common import result_payload, save_result
from repro.core.channel import WirelessConfig, make_deployment
from repro.core.bounds import ObjectiveWeights
from repro.core import ota_design, digital_design
# Objective-quality gate: jax <= scipy * (1 + PARITY_RTOL) per grid point.
from repro.core.sca_jax import ORACLE_RTOL as PARITY_RTOL
from repro import compile_cache


def _weight_grid(n_devices: int, grid: tuple[int, int]) -> list[ObjectiveWeights]:
    """(omega_var, omega_bias) trade-off grid around the fig2 operating point.

    Base weights follow the strongly convex rule (Sec. IV footnote 4) at
    the fig2 protocol's eta_max/mu/kappa_sc; the multipliers sweep the
    bias-variance trade-off log-spaced, as in the omega sweeps of the
    authors' companion OTA paper (arXiv:2403.19849).
    """
    eta, mu, kappa = 0.1, 0.01, 3.0
    base = ObjectiveWeights.strongly_convex(eta=eta, mu=mu, kappa_sc=kappa,
                                            n=n_devices)
    sv = np.logspace(-1.0, 1.0, grid[0])
    sb = np.logspace(-1.0, 1.0, grid[1])
    return [ObjectiveWeights(omega_var=base.omega_var * a,
                             omega_bias=base.omega_bias * b)
            for a in sv for b in sb]


def _bench_family(name, specs, scipy_solve, batch_solve, oracle_iters):
    t0 = time.perf_counter()
    scipy_objs = [scipy_solve(s, oracle_iters) for s in specs]
    scipy_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    _, jax_objs = batch_solve(specs)
    jax_cold_s = time.perf_counter() - t0          # includes jit compile
    t0 = time.perf_counter()
    _, jax_objs = batch_solve(specs)
    jax_warm_s = time.perf_counter() - t0

    scipy_objs = np.asarray(scipy_objs)
    jax_objs = np.asarray(jax_objs)
    rel_gap = (jax_objs - scipy_objs) / np.abs(scipy_objs)
    return {
        "family": name,
        "n_points": len(specs),
        "n_devices": specs[0].n,
        "oracle_n_iters": oracle_iters,
        "scipy_s": scipy_s,
        "scipy_s_per_point": scipy_s / len(specs),
        "jax_cold_s": jax_cold_s,
        "jax_warm_s": jax_warm_s,
        "jax_cold_s_per_point": jax_cold_s / len(specs),
        "speedup_cold": scipy_s / jax_cold_s,
        "speedup_warm": scipy_s / max(jax_warm_s, 1e-12),
        "scipy_objectives": scipy_objs.tolist(),
        "jax_objectives": jax_objs.tolist(),
        "max_rel_gap": float(np.max(rel_gap)),
        "parity_ok": bool(np.all(rel_gap <= PARITY_RTOL)),
    }


def run(quick: bool = True, *, n_devices: int = 50, grid: tuple = (4, 4),
        oracle_iters: int = 8, t_max_s: float = 0.2,
        result_name: str = "design_bench"):
    """Benchmark entry (also wired into benchmarks.run).

    Full mode is the fig2-sized sweep: N=50 devices, a 4x4
    (omega_var, omega_bias) grid (16 independent design points) per
    family, SCA oracle at the fig2 pipelines' n_iters=8. ``quick`` keeps
    the protocol but shrinks to N=20 and a 2x2 grid and records under
    ``design_bench_smoke`` so it never clobbers the fig2-sized artifact.
    """
    if quick:
        n_devices, grid, oracle_iters = 20, (2, 2), 4
        result_name = "design_bench_smoke"
    dep = make_deployment(WirelessConfig(n_devices=n_devices, seed=1))
    cfg = dep.cfg
    weights = _weight_grid(n_devices, grid)

    ota_specs = [ota_design.OTADesignSpec(
        lambdas=dep.lambdas, dim=7850, g_max=20.0,
        e_s=cfg.energy_per_symbol, n0=cfg.noise_power, weights=w)
        for w in weights]
    dig_specs = [digital_design.DigitalDesignSpec(
        lambdas=dep.lambdas, dim=7850, g_max=20.0,
        e_s=cfg.energy_per_symbol, n0=cfg.noise_power,
        bandwidth_hz=cfg.bandwidth_hz, t_max_s=t_max_s, weights=w)
        for w in weights]

    results = [
        _bench_family(
            "ota", ota_specs,
            lambda s, it: ota_design.design_ota_sca(s, n_iters=it)[1].objective,
            ota_design.design_ota_batch, oracle_iters),
        _bench_family(
            "digital", dig_specs,
            lambda s, it: digital_design.design_digital_sca(
                s, n_iters=it)[1].objective,
            digital_design.design_digital_batch, oracle_iters),
    ]
    payload = result_payload("design_bench", quick=quick, grid=list(grid),
                             n_devices=n_devices, parity_rtol=PARITY_RTOL,
                             results=results)
    save_result(result_name, payload)
    rows = [(f"design_bench/{r['family']}",
             r["jax_cold_s"] * 1e6 / r["n_points"],
             f"speedup={r['speedup_cold']:.1f}x;"
             f"max_rel_gap={r['max_rel_gap']:.1e}")
            for r in results]
    return rows, payload


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small grid CI guard: asserts the JAX path matches "
                         "or beats the SCA oracle on every point")
    args = ap.parse_args()
    compile_cache.enable()
    rows, payload = run(quick=args.smoke)
    print("family,n_points,scipy[s],jax_cold[s],jax_warm[s],speedup_cold,"
          "max_rel_gap")
    for r in payload["results"]:
        print(f"{r['family']},{r['n_points']},{r['scipy_s']:.2f},"
              f"{r['jax_cold_s']:.2f},{r['jax_warm_s']:.2f},"
              f"{r['speedup_cold']:.1f}x,{r['max_rel_gap']:+.2e}")
    if args.smoke:
        bad = [r for r in payload["results"] if not r["parity_ok"]]
        if bad:
            print("FAIL: batched JAX design solver lost to the SciPy SCA "
                  f"oracle beyond rtol {PARITY_RTOL} on: "
                  f"{[r['family'] for r in bad]}", file=sys.stderr)
            sys.exit(1)
        print("smoke OK: jax design objectives within "
              f"{PARITY_RTOL} of (or better than) the SCA oracle")


if __name__ == "__main__":
    main()
