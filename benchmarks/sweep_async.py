"""Buffered-async workload: staleness-priced design vs naive async vs sync.

Runs three equal-wall-clock variants of the ``sweep_async`` grid
(arrival-rate heterogeneity x buffer depth x staleness discount,
``core.async_fl``) with one class per device, so slow-arriving devices
starve their class — a structured staleness bias:

  * **designed**  — ``run.mode="async"`` with the bound-driven PS weights
    v from ``core.sca_jax.solve_async_batch`` and a staleness discount
    ``delta^S``: the priced operating point (the discount axis belongs to
    the design — the summary picks the best discount per cell).
  * **naive**     — the same async arrivals with uniform v and delta = 1:
    aggregate whatever lands, unweighted (the classic buffered-async
    baseline).
  * **sync**      — ``run.mode="sync"`` with a round deadline exactly one
    OTA upload long (d/B) and a straggler probability matched to the
    async grid's mean per-round miss rate: the synchronous-with-deadline
    alternative that discards every late update.

All three charge identical per-round uplink latency (OTA tau = d/B; the
deadline caps straggler stretch at exactly d/B), so equal rounds = equal
wall-clock — the summary asserts the measured ``wall_time_s`` agree and
reduces the grid to designed-minus-naive / designed-minus-sync
final-accuracy gains. A bound-validation section (the
``theorem_validation`` pattern) runs the K=1 regime — where delivery is
independent Bernoulli thinning and the Theorem-1 model is exact — and
checks the measured steady-state optimality error sits below the
Theorem-1 bound evaluated at the async effective participation levels
(``bounds.async_effective_participation``) with the analytic delivery
variance.

    PYTHONPATH=src python -m benchmarks.run --only sweep_async
    PYTHONPATH=src python -m benchmarks.sweep_async --smoke
    PYTHONPATH=src python -m repro.api.cli run sweep_async [--full]

Writes experiments/results/sweep_async.json (summary) on top of the
ResultSets under experiments/results/scenarios/sweep_async*/.
``--smoke`` exits non-zero unless the staleness-priced design strictly
beats BOTH naive async and the sync deadline on at least one cell at
equal wall-clock, the wall-clocks match, and every K=1 bound row holds
(the PR's acceptance gate).
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import numpy as np

from repro.api import execute
from repro.api.scenarios import sweep_async as make_spec
from repro.api.spec import FaultSpec, SweepSpec
from repro.core import async_fl, sca_jax
from repro.core import baselines as B
from repro.core.bounds import (ObjectiveWeights, async_bias_sum,
                               async_effective_participation, theorem1_bound)
from repro.fl.trainer import FLTrainer, solve_w_star

from .common import estimate_kappa_sc, make_sc_setup, save_result
from repro import compile_cache


def _variants(sweep: SweepSpec):
    """Derive the naive-async and sync-deadline comparison sweeps.

    Returns ``(naive, sync, miss_by_het)``: the naive grid drops the
    discount axis (delta = 1 IS the naive policy), the sync grid maps
    each heterogeneity value to the matched mean miss rate
    ``mean_m(1 - r_m)`` as a homogeneous straggler probability under a
    d/B deadline (late = lost, wall-clock unchanged).
    """
    base = sweep.base
    axes = dict(sweep.axes)
    hets = axes["async_.rate_heterogeneity"]
    bufs = axes["async_.buffer_rounds"]
    naive = SweepSpec(
        name="sweep_async_naive",
        base=base.replace(
            name="sweep_async_naive",
            async_=dataclasses.replace(base.async_, staleness_discount=1.0,
                                       weighting="uniform")),
        axes={"async_.rate_heterogeneity": hets,
              "async_.buffer_rounds": bufs})
    n = base.wireless.n_devices
    # OTA upload: tau = dim/B seconds (softmax dim = C*(F+1)); a deadline
    # of exactly tau keeps every round's realized latency at tau
    tau = (base.task.n_classes * (base.task.n_features + 1)
           / base.wireless.bandwidth_hz)
    miss_by_het = {
        h: round(float(np.mean(1.0 - async_fl.arrival_rates(
            dataclasses.replace(base.async_, rate_heterogeneity=h), n))), 9)
        for h in hets}
    sync = SweepSpec(
        name="sweep_async_sync",
        base=base.replace(
            name="sweep_async_sync",
            run=dataclasses.replace(base.run, mode="sync"),
            fault=FaultSpec(straggler_prob=miss_by_het[hets[0]],
                            straggler_mult=16.0, deadline_s=tau,
                            on_missing="zero")),
        axes={"fault.straggler_prob": tuple(miss_by_het[h] for h in hets)})
    return naive, sync, miss_by_het


def _finals(rs, scheme: str):
    """{overrides-tuple-free key: (final acc, final wall-clock)} per cell."""
    out = {}
    for cell in rs:
        rec = cell.log(scheme)
        out[tuple(sorted(cell.payload["overrides"].items()))] = (
            float(rec["acc_mean"][-1]), float(rec["wall_time_s"][-1]))
    return out


def _validate_bound(quick: bool):
    """K=1 bound rows: measured steady-state error vs Theorem 1.

    With ``buffer_rounds=1`` only fresh updates land, so the async layer
    is independent Bernoulli thinning with per-device keep probability
    ``c_m`` and payload scale ``v_m N / sum(cv)`` — exactly the regime
    Theorem 1 models: bias from the effective levels
    ``async_effective_participation``, variance bounded by the analytic
    delivery term ``G^2/N^2 sum(scale^2 c (1-c))``. Measured tail
    optimality error must sit below the bound for uniform AND designed
    weights, and the designed weights must not increase the priced bias
    sum (the solver's whole point).
    """
    rounds = 120 if quick else 300
    trials = 2
    tail = 3
    n = 8
    task, ds, dep, eta_max = make_sc_setup(
        n, samples_per_device=150 if quick else 600,
        n_train_per_class=200 if quick else 1200)
    eta = 0.25 * eta_max
    kappa = estimate_kappa_sc(task, ds)
    x_all = np.concatenate([d.x for d in ds.devices])
    y_all = np.concatenate([d.y for d in ds.devices])
    w_star = solve_w_star(task, x_all, y_all, iters=1500)
    ow = ObjectiveWeights.strongly_convex(eta=eta, mu=task.mu,
                                         kappa_sc=kappa, n=n)
    p = np.full(n, 1.0 / n)
    rows, val = [], []
    for het in (1.0, 3.0):
        asp = async_fl.AsyncSpec(buffer_rounds=1, arrival_rate=0.7,
                                 rate_heterogeneity=het)
        c = async_fl.delivery_weight(asp, n)
        sbar = async_fl.expected_staleness(asp, n)
        v_des, _ = sca_jax.solve_async_batch(
            p[None], c[None], sbar[None], [ow.omega_var], [ow.omega_bias])
        for wname, v in (("uniform", None), ("designed", v_des[0])):
            res = async_fl.resolve("async", asp, n, v)
            scale = res.payload_scale_array()
            e = async_effective_participation(p, c, v)
            zeta_del = float(task.g_max ** 2 / n ** 2
                             * np.sum(scale ** 2 * c * (1.0 - c)))
            bound = theorem1_bound(rounds, eta=eta, mu=task.mu, diam=0.0,
                                   kappa_sc=kappa, p=e, zeta=zeta_del)
            tr = FLTrainer(task, ds, dep, eta=eta, mode="async",
                           async_spec=asp, async_weights=v)
            log = tr.run(B.IdealFedAvg(), rounds=rounds, trials=trials,
                         eval_every=rounds // 6, seed=3, w_star=w_star)
            measured = float(log.opt_error[:, -tail:].mean())
            holds = measured <= bound["total"] + 1e-6
            val.append({"het": het, "weighting": wname,
                        "bias_sum": async_bias_sum(p, c, v),
                        "zeta_delivery": zeta_del,
                        "bound_bias": bound["bias"],
                        "bound_var": bound["variance"],
                        "bound_total": bound["total"],
                        "measured_err": measured, "holds": holds})
            rows.append((f"sweep_async/bound_het{het:g}_{wname}",
                         measured * 1e6,
                         f"bound={bound['total']:.3f};holds={holds}"))
    # the designed v must not inflate the priced bias vs uniform at the
    # solver's own operating point (bias-weighted objective)
    by_het = {}
    for r in val:
        by_het.setdefault(r["het"], {})[r["weighting"]] = r
    for het, d in by_het.items():
        d["designed"]["bias_reduced"] = bool(
            d["designed"]["bias_sum"] <= d["uniform"]["bias_sum"] + 1e-12)
    return rows, val


def run(quick: bool = True, n_devices: int = 10, use_cache: bool = True,
        jobs: int = 1):
    """Async-sweep entry: three equal-wall-clock variants + bound rows.
    Cache ON by default (interrupted runs resume from finished cells);
    ``use_cache=False`` forces a full recompute."""
    t0 = time.time()
    designed = make_spec(quick=quick, n_devices=n_devices)
    naive, sync, miss_by_het = _variants(designed)
    scheme = designed.base.schemes[0]
    rs_d = execute(designed, force=not use_cache, jobs=jobs)
    rs_n = execute(naive, force=not use_cache, jobs=jobs)
    rs_s = execute(sync, force=not use_cache, jobs=jobs)
    f_d = _finals(rs_d, scheme)
    f_n = _finals(rs_n, scheme)
    f_s = _finals(rs_s, scheme)

    axes = dict(designed.axes)
    hets = axes["async_.rate_heterogeneity"]
    bufs = axes["async_.buffer_rounds"]
    discs = axes["async_.staleness_discount"]
    sync_by_het = {
        h: f_s[tuple(sorted({"fault.straggler_prob":
                             miss_by_het[h]}.items()))]
        for h in hets}

    rows, comparison = [], {}
    walls = []
    for h in hets:
        for k in bufs:
            per_disc = {}
            for d in discs:
                acc, wall = f_d[tuple(sorted({
                    "async_.rate_heterogeneity": h,
                    "async_.buffer_rounds": k,
                    "async_.staleness_discount": d}.items()))]
                per_disc[d] = acc
                walls.append(wall)
            best_disc = max(per_disc, key=per_disc.get)
            des_acc = per_disc[best_disc]
            nai_acc, nai_wall = f_n[tuple(sorted({
                "async_.rate_heterogeneity": h,
                "async_.buffer_rounds": k}.items()))]
            syn_acc, syn_wall = sync_by_het[h]
            walls += [nai_wall, syn_wall]
            comparison[f"het{h:g}_K{k}"] = {
                "designed_acc": des_acc, "best_discount": best_disc,
                "designed_by_discount": per_disc,
                "naive_acc": nai_acc, "sync_acc": syn_acc,
                "gain_vs_naive": des_acc - nai_acc,
                "gain_vs_sync": des_acc - syn_acc,
            }
            rows.append((f"sweep_async/het{h:g}_K{k}", 0.0,
                         f"designed={des_acc:.4f} naive={nai_acc:.4f} "
                         f"sync={syn_acc:.4f}"))

    wall_spread = float(np.max(walls) - np.min(walls))
    equal_wall = wall_spread <= 1e-6 * max(float(np.max(walls)), 1e-12)
    best_vs_naive = max(c["gain_vs_naive"] for c in comparison.values())
    best_vs_sync = max(c["gain_vs_sync"] for c in comparison.values())
    brows, val = _validate_bound(quick)
    rows += brows
    payload = {"quick": quick, "n_devices": n_devices,
               "sweep": designed.to_dict(),
               "sweep_hash": designed.spec_hash(),
               "naive_hash": naive.spec_hash(),
               "sync_hash": sync.spec_hash(),
               "miss_by_het": {f"{h:g}": q for h, q in miss_by_het.items()},
               "comparison": comparison,
               "best_gain_vs_naive": float(best_vs_naive),
               "best_gain_vs_sync": float(best_vs_sync),
               "wall_clock_spread_s": wall_spread,
               "equal_wall_clock": bool(equal_wall),
               "bound_validation": val,
               "all_cached": rs_d.all_cached and rs_n.all_cached
               and rs_s.all_cached,
               "elapsed_s": time.time() - t0}
    save_result("sweep_async", payload)
    return rows, payload


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="toy-scale CI gate (the quick grid; exits "
                         "non-zero unless the staleness-priced design "
                         "strictly beats naive async AND the sync "
                         "deadline on >= 1 cell at equal wall-clock, "
                         "and every K=1 bound row holds)")
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
    for key, c in payload["comparison"].items():
        print(f"{key}: designed {c['designed_acc']:.4f} "
              f"(delta*={c['best_discount']:g}) vs naive "
              f"{c['naive_acc']:.4f} ({c['gain_vs_naive']:+.4f}) vs sync "
              f"{c['sync_acc']:.4f} ({c['gain_vs_sync']:+.4f})")
    print(f"best gain vs naive: {payload['best_gain_vs_naive']:+.4f}; "
          f"vs sync: {payload['best_gain_vs_sync']:+.4f}; wall spread "
          f"{payload['wall_clock_spread_s']:.3g}s")
    if args.smoke:
        failures = []
        if not payload["best_gain_vs_naive"] > 0.0:
            failures.append("designed never beat naive async")
        if not payload["best_gain_vs_sync"] > 0.0:
            failures.append("designed never beat the sync deadline")
        if not payload["equal_wall_clock"]:
            failures.append("wall-clocks diverged across variants")
        if not all(r["holds"] for r in payload["bound_validation"]):
            failures.append("a Theorem-1 bound row failed")
        if failures:
            print("FAIL: " + "; ".join(failures), file=sys.stderr)
            sys.exit(1)


if __name__ == "__main__":
    main()
