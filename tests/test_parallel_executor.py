"""Parallel sweep executor contracts (``execute(..., jobs=K)``).

The process pool must be *invisible* in the artifacts: a ``jobs=2`` run
of a sweep produces a manifest and per-cell payloads identical to the
serial run (modulo wall-clock timings), cached re-runs stay no-ops
without spawning anything, a half-finished sweep resumes from the cells
that completed — including when the unfinished half died inside a
worker — and the dependency-ordered schedule keeps every design-group
solve ahead of its dependent cells. The supervisor hardening rides the
same contracts: a SIGKILLed worker's cell is requeued and the manifest
still matches serial; a hung cell surfaces as ``status="timeout"``
instead of wedging the sweep; a corrupt cache cell is quarantined to
``<hash>.json.bad`` and recomputed.

(These tests live in a real file on purpose: the pool uses the spawn
start method, which re-imports ``__main__`` in each worker.)
"""
import json

import jax
import pytest

from repro.api import ScenarioSpec, SweepSpec, execute, plan
from repro.api.spec import DataSpec, DesignPolicy, RunSpec
from repro.core.channel import WirelessConfig
from repro.fl.trainer import FLTrainer

N_DEVICES = 6


def _tiny(**over) -> ScenarioSpec:
    """Seconds-scale scenario (mirrors test_scenario_api's tiny cell)."""
    kw = dict(
        name="tiny_par",
        data=DataSpec(n_train_per_class=60, n_test_per_class=20,
                      samples_per_device=60),
        wireless=WirelessConfig(n_devices=N_DEVICES, seed=1),
        design=DesignPolicy(kappa=3.0),
        run=RunSpec(rounds=6, trials=1, eval_every=3, etas=(1.0,),
                    backend="numpy"),
        schemes=("proposed_ota", "vanilla_ota"))
    kw.update(over)
    return ScenarioSpec(**kw)


def _grid() -> SweepSpec:
    """2x2 grid with a designed scheme: exercises the design-pack path."""
    return SweepSpec(name="par_grid", base=_tiny(),
                     axes={"wireless.tx_power_dbm": (-3.0, 3.0),
                           "design.omega_bias_scale": (1.0, 2.0)})


def _strip(obj):
    """Drop wall-clock fields recursively (the only sanctioned delta)."""
    if isinstance(obj, dict):
        return {k: _strip(v) for k, v in obj.items() if k != "elapsed_s"}
    if isinstance(obj, (list, tuple)):
        return [_strip(v) for v in obj]
    return obj


def test_parallel_manifest_matches_serial(tmp_path):
    sweep = _grid()
    rs_ser = execute(sweep, out_dir=tmp_path / "serial")
    rs_par = execute(sweep, out_dir=tmp_path / "par", jobs=2)
    assert [c.status for c in rs_par] == ["computed"] * 4
    assert _strip(rs_par.manifest) == _strip(rs_ser.manifest)
    for cs, cp in zip(rs_ser, rs_par):
        assert cp.cell_hash == cs.cell_hash
        assert _strip(cp.payload) == _strip(cs.payload)
    # and so are the artifacts both runs put on disk
    for cp in rs_par:
        a = json.loads(cp.path.read_text())
        b = json.loads((tmp_path / "serial" / "cells"
                        / f"{cp.cell_hash}.json").read_text())
        assert _strip(a) == _strip(b)


def test_parallel_cached_rerun_is_noop(tmp_path, monkeypatch):
    sweep = _grid()
    out = tmp_path / "rs"
    execute(sweep, out_dir=out, jobs=2)

    def boom(*a, **k):
        raise AssertionError("cached parallel re-run must not simulate")

    # with every cell cached there is nothing to pool — the stubbed
    # trainer proves no simulation happens in-process either
    monkeypatch.setattr(FLTrainer, "run", boom)
    rs = execute(sweep, out_dir=out, jobs=2)
    assert rs.all_cached


def test_parallel_resumes_partial_sweep(tmp_path):
    """Serial half-sweep, then the full grid with jobs=2: the finished
    cells load from cache, only the missing half hits the pool."""
    base = _tiny()
    half = SweepSpec(name="par_grid", base=base,
                     axes={"wireless.tx_power_dbm": (-3.0,),
                           "design.omega_bias_scale": (1.0, 2.0)})
    out = tmp_path / "rs"
    execute(half, out_dir=out)
    rs = execute(_grid(), out_dir=out, jobs=2)
    statuses = {c.overrides["wireless.tx_power_dbm"]: c.status for c in rs}
    assert [c.status for c in rs].count("cached") == 2
    assert statuses[-3.0] == "cached" and statuses[3.0] == "computed"


def test_worker_failure_is_collected_and_resumable(tmp_path):
    """One cell fails inside a worker (invalid run.rng only trips at run
    time): execute raises *after* collecting, the good cell's artifact is
    on disk, and a corrected re-run resumes from it."""
    base = _tiny(schemes=("vanilla_ota",))
    bad = SweepSpec(name="par_bad", base=base,
                    axes={"run.rng": ("replay", "bogus")})
    out = tmp_path / "rs"
    with pytest.raises(RuntimeError, match="failed in workers"):
        execute(bad, out_dir=out, jobs=2)
    good_hash = plan(SweepSpec(name="par_bad", base=base,
                               axes={"run.rng": ("replay",)})).cells[0] \
        .cell_hash
    assert (out / "cells" / f"{good_hash}.json").exists()
    rs = execute(SweepSpec(name="par_bad", base=base,
                           axes={"run.rng": ("replay",)}),
                 out_dir=out, jobs=2)
    assert [c.status for c in rs] == ["cached"]


def test_jobs_validation(tmp_path):
    with pytest.raises(ValueError, match="jobs"):
        execute(_tiny(), out_dir=tmp_path / "rs", jobs=0)
    with pytest.raises(ValueError, match="retries"):
        execute(_tiny(), out_dir=tmp_path / "rs", retries=-1)
    with pytest.raises(ValueError, match="cell_timeout_s"):
        execute(_tiny(), out_dir=tmp_path / "rs", cell_timeout_s=0.0)


def test_chaos_worker_kill_is_recovered(tmp_path, monkeypatch):
    """SIGKILL one worker holding a cell (env-gated chaos hook, fires
    exactly once): the supervisor requeues the cell on a fresh worker and the
    sweep completes with a manifest identical to the serial run."""
    sweep = _grid()
    rs_ser = execute(sweep, out_dir=tmp_path / "serial")
    kill_dir = tmp_path / "chaos"
    kill_dir.mkdir()
    monkeypatch.setenv("REPRO_CHAOS_KILL_DIR", str(kill_dir))
    rs_par = execute(sweep, out_dir=tmp_path / "par", jobs=2)
    assert (kill_dir / "killed").exists(), "chaos hook never fired"
    assert [c.status for c in rs_par] == ["computed"] * 4
    assert _strip(rs_par.manifest) == _strip(rs_ser.manifest)
    for cs, cp in zip(rs_ser, rs_par):
        assert _strip(cp.payload) == _strip(cs.payload)


def test_chaos_worker_crash_exhausts_retries_and_raises(tmp_path,
                                                        monkeypatch):
    """With retries=0, a killed worker's cell has no second chance: the
    sweep raises (crash != timeout — losing a worker with retries
    exhausted is an error, not a quietly missing cell)."""
    kill_dir = tmp_path / "chaos"
    kill_dir.mkdir()
    monkeypatch.setenv("REPRO_CHAOS_KILL_DIR", str(kill_dir))
    with pytest.raises(RuntimeError, match="failed in workers"):
        execute(_tiny(), out_dir=tmp_path / "rs", jobs=2, retries=0)


def test_chaos_hung_cell_times_out_not_hangs(tmp_path, monkeypatch):
    """A cell that never returns surfaces as status="timeout" (empty
    payload, no cells/<hash>.json, no exception) instead of wedging the
    sweep; the other cell of the grid still completes."""
    base = _tiny(schemes=("vanilla_ota",))
    sweep = SweepSpec(name="par_hang", base=base,
                      axes={"wireless.tx_power_dbm": (-3.0, 3.0)})
    hang_hash = plan(sweep).cells[0].cell_hash
    monkeypatch.setenv("REPRO_CHAOS_HANG_HASH", hang_hash)
    out = tmp_path / "rs"
    rs = execute(sweep, out_dir=out, jobs=2, cell_timeout_s=1.5, retries=0)
    by_hash = {c.cell_hash: c for c in rs}
    hung = by_hash[hang_hash]
    assert hung.status == "timeout" and hung.payload == {}
    assert hung.path is None
    assert not (out / "cells" / f"{hang_hash}.json").exists()
    others = [c for c in rs if c.cell_hash != hang_hash]
    assert [c.status for c in others] == ["computed"]
    manifest = json.loads((out / "manifest.json").read_text())
    row = next(r for r in manifest["cells"] if r["cell_hash"] == hang_hash)
    assert row["status"] == "timeout" and row["elapsed_s"] is None
    # the timed-out cell is not cached: a clean re-run computes it
    monkeypatch.delenv("REPRO_CHAOS_HANG_HASH")
    rs2 = execute(sweep, out_dir=out, jobs=2)
    assert {c.cell_hash: c.status for c in rs2} == {
        hang_hash: "computed", others[0].cell_hash: "cached"}


def test_corrupt_cache_cell_is_quarantined_and_recomputed(tmp_path):
    """A truncated/corrupt cells/<hash>.json must not poison the sweep:
    it is moved to <hash>.json.bad and the cell recomputes."""
    out = tmp_path / "rs"
    rs = execute(_tiny(), out_dir=out)
    cell = rs.cells[0]
    path = out / "cells" / f"{cell.cell_hash}.json"
    path.write_text('{"schema_version": 5, "truncated')
    rs2 = execute(_tiny(), out_dir=out)
    assert rs2.cells[0].status == "computed"
    bad = out / "cells" / f"{cell.cell_hash}.json.bad"
    assert bad.exists()
    assert bad.read_text().startswith('{"schema_version": 5, "truncated')
    # the fresh artifact is valid JSON again and a re-run is a cache hit
    json.loads(path.read_text())
    assert execute(_tiny(), out_dir=out).cells[0].status == "cached"


def test_schedule_orders_designs_before_dependent_cells():
    """Every design group appears in the schedule before any cell that
    needs its parameters — the invariant both executors walk."""
    pl = plan(_grid())
    assert pl.design_groups
    solved = set()
    seen_cells = set()
    for kind, item in pl.schedule():
        if kind == "design":
            assert not (set(item.cell_indices) & seen_cells), \
                "design group scheduled after a dependent cell"
            solved.add(id(item))
        else:
            seen_cells.add(item.index)
    assert len(solved) == len(pl.design_groups)
    assert len(seen_cells) == len(pl.cells)


def test_parallel_refused_on_accelerator(tmp_path, monkeypatch):
    """On an accelerator backend the pool would spawn workers that each
    need the chip, which one process already holds: ``jobs>1`` raises
    before any worker (or any cell) starts."""
    import multiprocessing

    def no_spawn(*a, **k):
        raise AssertionError("a worker was spawned")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(multiprocessing, "get_context", no_spawn)
    with pytest.raises(RuntimeError, match="jobs=1"):
        execute(_tiny(), out_dir=tmp_path / "r", jobs=2)
    assert not (tmp_path / "r").exists()
