#!/usr/bin/env python3
"""Readings that set a cell's correctness limits (run on the chip).

    python3 bench/control.py --workload <cell> --seeds 12 --control-seeds 3 \
        [--faults frozen,half_batch,altered --fault-seeds 3]

In one process: the program's calls on ``--seeds`` seeds against the plain
reference (the lower readings: sound runs), then the cell's control on
``--control-seeds`` further seeds against the same reference (the upper
readings), then each planted fault (``bench/faults.py``) on the first
``--fault-seeds`` of the sound seeds, at the cell's own size. The control, named in ``bench/limits/<cell>.json``, is the
reference itself put in the program's place at the next precision below
the configuration's: ``high`` (three bfloat16 passes) for float32
products at ``highest``, ``bf16`` payloads for float32 payloads.

Prints one JSON line: every reading of every seed and, per number, the
largest sound reading and the smallest control reading. The benchmark's
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings_for(reg, workload: str, seeds: int, control_seeds: int,
                 base_seed: int, faults=(), fault_seeds: int = 3) -> dict:
    from repro import compile_cache

    from bench import faults as F, fl

    compile_cache.enable()
    cell_entry = reg.cell(workload)
    config = reg.config(cell_entry["config"])
    traffic = reg.traffic(cell_entry["traffic"])
    control = reg.limits(workload)["control"]
    cfgmod = reg.module("configs", cell_entry["config"])
    ref_mod = reg.module("configs", cell_entry["config"] + "_ref")
    data = cfgmod.dataset(config)
    reference = ref_mod.Reference(config, traffic)

    refs: dict = {}

    def want(seed):
        if seed not in refs:
            refs[seed] = reference.run(seed)
        return refs[seed]

    def program_readings(program, n):
        eng, agg, kw = program(config, traffic, data)
        cell = fl.Cell(eng, agg, kw, base_seed, keep=0)
        out = []
        for i in range(n):
            seed = fl.call_seed(base_seed, i)
            got = fl.to_host(cell.run(seed))
            out.append(dict(fl.readings(got, want(seed)), seed=seed))
        return out

    t = time.perf_counter()
    sound = program_readings(cfgmod.program, seeds)
    ctl = ref_mod.Reference(config, traffic, precision=control["precision"])
    upper = []
    for i in range(seeds, seeds + control_seeds):
        seed = fl.call_seed(base_seed, i)
        upper.append(dict(fl.readings(ctl.run(seed), reference.run(seed)),
                          seed=seed))
    keys = [k for k in sound[0] if k != "seed"]
    planted = {}
    for how in faults:
        runs = program_readings(F.broken(cfgmod.program, how), fault_seeds)
        planted[how] = {"smallest": {k: min(r[k] for r in runs)
                                     for k in keys}, "runs": runs}
    return {"workload": workload, "control": control,
            "seconds": time.perf_counter() - t,
            "lower": {k: max(r[k] for r in sound) for k in keys},
            "upper": {k: min(r[k] for r in upper) for k in keys} if upper
            else {},
            "faults": planted, "sound": sound, "control_runs": upper}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seed", type=int, default=20261017)
    ap.add_argument("--faults", default="",
                    help="comma-separated faults of bench/faults.py")
    ap.add_argument("--fault-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    from bench.registry import Registry
    faults = [f for f in args.faults.split(",") if f]
    print(json.dumps(readings_for(Registry(), args.workload, args.seeds,
                                  args.control_seeds, args.seed, faults,
                                  args.fault_seeds)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
