"""Partial-participation workload: N x S grid, uniform vs designed sampling.

Runs the ``sweep_participation`` grid (device population x expected
cohort size x sampling policy — ``core.participation``) under
heterogeneous channel-dependent deep fades with zero-fill degradation:
every cell samples an expected S = ``run.clients_per_round`` of the N
devices per round, so the "uniform" (pi = S/N, exact zero sampling bias)
and "designed" (bound-driven capped-simplex pi,
``core.sca_jax.solve_participation_batch``) policies spend EQUAL expected
airtime. The summary reduces each (N, S, scheme) cell pair to the
designed-minus-uniform final-accuracy gain. The thesis: with one class
per device, uniform sampling starves the devices the fades already
starve (effective level p*pi*q collapses), while the co-designed pi
re-balances the effective participation the Theorem-1/2 bound prices —
a strictly better model at the same sampling budget.

    PYTHONPATH=src python -m benchmarks.run --only sweep_participation
    PYTHONPATH=src python -m benchmarks.sweep_participation --smoke
    PYTHONPATH=src python -m repro.api.cli run sweep_participation [--full]

Writes experiments/results/sweep_participation.json (summary) on top of
the ResultSet under experiments/results/scenarios/sweep_participation/.
``--smoke`` exits non-zero unless the designed policy strictly beats
uniform on at least one heterogeneous cell (the PR's acceptance gate).
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time

from repro.api import execute
from repro.api.scenarios import sweep_participation as make_spec

from .common import save_result
from repro import compile_cache


def run(quick: bool = True, n_devices: int = 50, use_cache: bool = True,
        jobs: int = 1):
    """Participation-sweep entry. Cache ON by default (sweep-workload
    semantics: interrupted runs resume from finished cells);
    ``use_cache=False`` forces a full recompute."""
    t0 = time.time()
    sweep = make_spec(quick=quick, n_devices=n_devices)
    rs = execute(sweep, force=not use_cache, jobs=jobs)
    schemes = tuple(sweep.base.schemes)
    rows, cells = [], []
    by_cell: dict = {}
    for cell in rs:
        p = cell.payload
        recs = {rec["scheme_key"]: rec for rec in p["logs"]}
        finals = {k: rec["acc_mean"][-1] for k, rec in recs.items()}
        n = p["overrides"]["wireless.n_devices"]
        s = p["overrides"]["run.clients_per_round"]
        policy = p["overrides"]["run.participation"]
        by_cell.setdefault((n, s), {})[policy] = finals
        cells.append({
            "overrides": p["overrides"], "cell_hash": p["cell_hash"],
            "final_acc": finals,
            "design_objectives": {f: d["objective"]
                                  for f, d in p["design"].items()},
            "status": cell.status,
        })
        rows.append((f"sweep_participation/n{n}_s{s}_{policy}",
                     p["elapsed_s"] * 1e6,
                     " ".join(f"{k}={v:.4f}" for k, v in sorted(
                         finals.items()))))
    # equal-airtime comparison: designed-minus-uniform final accuracy per
    # (N, S) cell and scheme; S == N cells sample everyone under either
    # policy, so their gain is ~0 and never carries the domination claim
    gains = {}
    for (n, s), pols in sorted(by_cell.items()):
        if "uniform" not in pols or "designed" not in pols:
            continue
        gains[f"n{n}_s{s}"] = {
            k: pols["designed"][k] - pols["uniform"][k]
            for k in schemes}
    best_gain = float(max((v for g in gains.values() for v in g.values()),
                          default=float("-inf")))
    payload = {"quick": quick, "n_devices": n_devices,
               "sweep": sweep.to_dict(), "sweep_hash": sweep.spec_hash(),
               "fault": dataclasses.asdict(sweep.base.fault),
               "n_cells": len(cells), "cells": cells,
               "designed_minus_uniform": gains,
               "best_designed_gain": best_gain,
               "all_cached": rs.all_cached, "elapsed_s": time.time() - t0}
    save_result("sweep_participation", payload)
    return rows, payload


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="toy-scale CI gate (the quick grid; exits "
                         "non-zero unless designed sampling strictly "
                         "beats uniform on >= 1 cell)")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale grid (slow)")
    ap.add_argument("--jobs", type=int, default=1, metavar="K",
                    help="worker-pool size for the sweep cells")
    args = ap.parse_args()
    compile_cache.enable()
    quick = not args.full or args.smoke
    rows, payload = run(quick=quick, jobs=args.jobs)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}", flush=True)
    for key, g in payload["designed_minus_uniform"].items():
        print(key + ": " + ", ".join(
            f"{k} designed-uniform {v:+.4f}" for k, v in sorted(g.items())))
    best = payload["best_designed_gain"]
    print(f"best designed-vs-uniform gain: {best:+.4f}")
    if args.smoke and not best > 0.0:
        print("FAIL: designed sampling never beat uniform at equal airtime",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
