"""Fault-injection workload: outage rate x heterogeneity sweep.

Runs the ``sweep_fault`` grid (per-round dropout probability x path-loss
exponent, with a deep-fade cutoff active throughout — ``core.faults``)
comparing the proposed biased OTA design, whose solver sees the
outage-adjusted effective channel statistics, against the zero-bias
Vanilla OTA baseline. The summary reduces each heterogeneity column to a
graceful-degradation record: how much final accuracy each scheme loses
going from the fault-free cell to the highest outage rate. The thesis:
the biased design degrades gracefully where zero-bias aggregation —
whose common pre-scaler chases the weakest instantaneous channel —
collapses.

    PYTHONPATH=src python -m benchmarks.run --only sweep_fault
    PYTHONPATH=src python -m benchmarks.sweep_fault --smoke
    PYTHONPATH=src python -m repro.api.cli run sweep_fault [--full]

Writes experiments/results/sweep_fault.json (summary) on top of the
ResultSet under experiments/results/scenarios/sweep_fault/.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

from repro.api import execute
from repro.api.scenarios import sweep_fault as make_spec

from .common import save_result
from repro import compile_cache


def run(quick: bool = True, n_devices: int = 10, use_cache: bool = True,
        jobs: int = 1):
    """Fault-sweep entry. Cache ON by default (sweep-workload semantics:
    interrupted runs resume from finished cells); ``use_cache=False``
    forces a full recompute."""
    t0 = time.time()
    sweep = make_spec(quick=quick, n_devices=n_devices)
    rs = execute(sweep, force=not use_cache, jobs=jobs)
    rows, cells = [], []
    by_pl: dict = {}
    for cell in rs:
        p = cell.payload
        recs = {rec["scheme_key"]: rec for rec in p["logs"]}
        finals = {k: rec["acc_mean"][-1] for k, rec in recs.items()}
        drop = p["overrides"]["fault.dropout_prob"]
        pl = p["overrides"]["wireless.pl_exponent"]
        # OTA rounds cost identical airtime (d/B), so the fixed-round
        # comparison is already latency-matched
        gain = finals["proposed_ota"] - finals["vanilla_ota"]
        by_pl.setdefault(pl, {})[drop] = finals
        cells.append({
            "overrides": p["overrides"], "cell_hash": p["cell_hash"],
            "final_acc": finals,
            "ota_gain_vs_zero_bias": gain,
            "design_objectives": {f: d["objective"]
                                  for f, d in p["design"].items()},
            "status": cell.status,
        })
        rows.append((f"sweep_fault/drop{drop:g}_pl{pl:g}",
                     p["elapsed_s"] * 1e6, f"ota_gain={gain:+.4f}"))
    # graceful-degradation summary: per heterogeneity column, accuracy
    # lost between the fault-free cell and the highest outage rate
    degradation = {}
    for pl, col in sorted(by_pl.items()):
        lo, hi = min(col), max(col)
        degradation[f"pl{pl:g}"] = {
            "dropout_lo": lo, "dropout_hi": hi,
            "proposed_acc_drop": (col[lo]["proposed_ota"]
                                  - col[hi]["proposed_ota"]),
            "vanilla_acc_drop": (col[lo]["vanilla_ota"]
                                 - col[hi]["vanilla_ota"]),
            "gain_at_hi_outage": (col[hi]["proposed_ota"]
                                  - col[hi]["vanilla_ota"]),
        }
    payload = {"quick": quick, "n_devices": n_devices,
               "sweep": sweep.to_dict(), "sweep_hash": sweep.spec_hash(),
               "fault": dataclasses.asdict(sweep.base.fault),
               "n_cells": len(cells), "cells": cells,
               "degradation": degradation,
               "all_cached": rs.all_cached, "elapsed_s": time.time() - t0}
    save_result("sweep_fault", payload)
    return rows, payload


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="toy-scale CI gate (the quick 2x2 grid; exits "
                         "non-zero on any failed cell)")
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
    for pl, d in payload["degradation"].items():
        print(f"{pl}: dropout {d['dropout_lo']:g}->{d['dropout_hi']:g}: "
              f"proposed loses {d['proposed_acc_drop']:+.4f} acc, "
              f"vanilla loses {d['vanilla_acc_drop']:+.4f} "
              f"(gain at high outage {d['gain_at_hi_outage']:+.4f})")


if __name__ == "__main__":
    main()
