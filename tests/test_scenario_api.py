"""Scenario/Sweep API contract tests.

Covers the four planner/executor guarantees plus serialization:

  * ``ScenarioSpec``/``SweepSpec`` dict <-> object round-trip (exhaustive
    hypothesis property + a hand-written case without hypothesis),
  * planner grouping: a K-point grid issues exactly ONE batched design
    solve per scheme family (no per-point solver calls),
  * content-hash caching: re-executing a finished sweep touches neither
    the design solvers nor the trainer,
  * legacy parity: a 2-point sweep through ``execute()`` reproduces the
    hand-rolled fig2-style pipeline (make_sc_setup -> design_ota ->
    suite -> run_tuned) trajectory-for-trajectory at matching seeds,
  * the strict result encoder (numpy conversions; raises on unknown).
"""
import dataclasses
import json

import numpy as np
import pytest

from repro.api import (ScenarioSpec, SweepSpec, execute, plan,
                       spec_from_dict)
from repro.api.results import SCHEMA_VERSION, dump_json
from repro.api.spec import (DataSpec, DesignPolicy, RunSpec, TaskSpec,
                            spec_hash)
from repro.core import digital_design, ota_design
from repro.core.channel import WirelessConfig
from repro.fl.trainer import FLTrainer

N_DEVICES = 6


def _tiny_scenario(**over) -> ScenarioSpec:
    """A seconds-scale scenario: toy data, fixed kappa, single-point etas."""
    kw = dict(
        name="tiny",
        data=DataSpec(n_train_per_class=60, n_test_per_class=20,
                      samples_per_device=60),
        wireless=WirelessConfig(n_devices=N_DEVICES, seed=1),
        design=DesignPolicy(kappa=3.0),
        run=RunSpec(rounds=6, trials=1, eval_every=3, etas=(1.0,),
                    backend="numpy"),
        schemes=("proposed_ota", "vanilla_ota"))
    kw.update(over)
    return ScenarioSpec(**kw)


# ------------------------------------------------------------ round-trip

def test_round_trip_hand_written():
    spec = _tiny_scenario()
    recovered = ScenarioSpec.from_dict(json.loads(json.dumps(spec.to_dict())))
    assert recovered == spec
    assert recovered.spec_hash() == spec.spec_hash()

    sweep = SweepSpec(name="s", base=spec,
                      axes={"wireless.tx_power_dbm": (-3.0, 3.0),
                            "run.rounds": (4, 8)})
    recovered = SweepSpec.from_dict(json.loads(json.dumps(sweep.to_dict())))
    assert recovered == sweep
    assert spec_from_dict(sweep.to_dict()) == sweep
    assert spec_from_dict(spec.to_dict()) == spec


def test_round_trip_hypothesis():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")
    given, settings = hyp.given, hyp.settings

    floats = st.floats(allow_nan=False, allow_infinity=False,
                       min_value=-1e6, max_value=1e6)
    pos = st.floats(min_value=1e-3, max_value=1e3)
    ints = st.integers(min_value=1, max_value=1000)

    scenarios = st.builds(
        ScenarioSpec,
        name=st.text(min_size=1, max_size=12),
        task=st.builds(TaskSpec, kind=st.sampled_from(("softmax", "mlp")),
                       n_features=ints, hidden=ints, mu=pos, g_max=pos),
        data=st.builds(DataSpec,
                       image_shape=st.tuples(ints, ints, ints),
                       n_train_per_class=ints, samples_per_device=ints,
                       noise_sigma=pos, dataset_seed=ints,
                       partition_seed=ints),
        wireless=st.builds(WirelessConfig, n_devices=ints,
                           tx_power_dbm=floats, pl_exponent=pos,
                           seed=ints),
        design=st.builds(DesignPolicy,
                         objective=st.sampled_from(
                             ("strongly_convex", "non_convex")),
                         kappa=st.one_of(st.none(), pos),
                         omega_bias_scale=pos, omega_var_scale=pos,
                         t_max_s=pos, top_k=ints),
        run=st.builds(RunSpec, rounds=ints, trials=ints, seed=ints,
                      etas=st.tuples(pos, pos),
                      eta_max=st.one_of(st.none(), pos),
                      batch_size=st.one_of(st.none(), ints),
                      time_budget_s=st.one_of(st.none(), pos)),
        schemes=st.tuples(st.sampled_from(
            ("ideal", "proposed_ota", "vanilla_ota", "suite:fig2_ota"))))

    @settings(max_examples=50, deadline=None)
    @given(spec=scenarios,
           axes=st.dictionaries(
               st.sampled_from(("wireless.tx_power_dbm",
                                "design.omega_bias_scale", "run.rounds")),
               st.lists(floats, min_size=1, max_size=3, unique=True),
               max_size=2))
    def check(spec, axes):
        # object -> dict -> JSON -> dict -> object is the identity
        rt = ScenarioSpec.from_dict(json.loads(json.dumps(spec.to_dict())))
        assert rt == spec
        sweep = SweepSpec(name="p", base=spec, axes=axes)
        rt = SweepSpec.from_dict(json.loads(json.dumps(sweep.to_dict())))
        assert rt == sweep
        assert rt.spec_hash() == sweep.spec_hash()
        assert len(sweep.points()) == sweep.n_points

    check()


def test_override_paths_and_hash_sensitivity():
    spec = _tiny_scenario()
    assert spec.override("wireless.tx_power_dbm", 7.0) \
               .wireless.tx_power_dbm == 7.0
    assert spec.override("run.rounds", 11).run.rounds == 11
    assert spec.override("design.omega_bias_scale", 2.0) \
               .design.omega_bias_scale == 2.0
    with pytest.raises(KeyError):
        spec.override("wireless.nope", 1)
    # content hash distinguishes any changed field
    assert spec.spec_hash() != spec.override("run.seed", 6).spec_hash()
    assert spec_hash(spec.to_dict()) == spec.spec_hash()


def test_numpy_valued_axes_hash_and_plan():
    """np.arange/np.linspace grids are the natural way to declare sweeps;
    hashing must treat numpy scalars like their Python equivalents."""
    base = _tiny_scenario()
    sweep_np = SweepSpec(name="s", base=base,
                         axes={"run.rounds": np.arange(10, 40, 10),
                               "wireless.tx_power_dbm":
                                   np.linspace(-5.0, 5.0, 2)})
    sweep_py = SweepSpec(name="s", base=base,
                         axes={"run.rounds": (10, 20, 30),
                               "wireless.tx_power_dbm": (-5.0, 5.0)})
    assert sweep_np.spec_hash() == sweep_py.spec_hash()
    pl = plan(sweep_np)
    assert len(pl.cells) == 6
    assert [c.cell_hash for c in pl.cells] == \
           [c.cell_hash for c in plan(sweep_py).cells]


# --------------------------------------------------------------- planner

def test_planner_groups_one_batched_solve_per_family():
    base = _tiny_scenario(schemes=("proposed_ota", "proposed_digital"))
    sweep = SweepSpec(name="grid", base=base,
                      axes={"design.omega_bias_scale": (0.5, 1.0, 2.0)})
    pl = plan(sweep)
    assert len(pl.cells) == 3
    assert len(pl.design_groups) == 2            # one per family
    by_family = {g.family: g for g in pl.design_groups}
    assert set(by_family) == {"ota", "digital"}
    for g in by_family.values():
        assert g.batched
        assert g.cell_indices == (0, 1, 2)
        assert g.needs_direct == ()


def test_execute_batches_designs_once_per_family(tmp_path, monkeypatch):
    """K grid points -> exactly one design_*_batch call per family, each
    carrying all K specs (the vmapped sweep-solver contract)."""
    calls = {"ota": [], "digital": []}
    real_ota, real_dig = (ota_design.design_ota_batch,
                          digital_design.design_digital_batch)
    monkeypatch.setattr(
        ota_design, "design_ota_batch",
        lambda specs, **kw: calls["ota"].append(len(specs)) or
        real_ota(specs, **kw))
    monkeypatch.setattr(
        digital_design, "design_digital_batch",
        lambda specs, **kw: calls["digital"].append(len(specs)) or
        real_dig(specs, **kw))

    base = _tiny_scenario(schemes=("proposed_ota", "proposed_digital"))
    sweep = SweepSpec(name="grid", base=base,
                      axes={"design.omega_bias_scale": (0.5, 1.0, 2.0)})
    rs = execute(sweep, out_dir=tmp_path / "rs")
    assert calls == {"ota": [3], "digital": [3]}   # one batched call each
    assert len(rs) == 3
    assert all(c.status == "computed" for c in rs)
    # designs landed per cell and differ across the omega axis
    objs = [c.payload["design"]["ota"]["objective"] for c in rs]
    assert len(set(objs)) == 3


# --------------------------------------------------------------- caching

def test_cache_hit_short_circuits(tmp_path, monkeypatch):
    base = _tiny_scenario()
    sweep = SweepSpec(name="cache", base=base,
                      axes={"design.omega_bias_scale": (1.0, 2.0)})
    out = tmp_path / "rs"
    rs1 = execute(sweep, out_dir=out)
    assert [c.status for c in rs1] == ["computed", "computed"]
    assert (out / "manifest.json").exists()

    def boom(*a, **k):
        raise AssertionError("cached re-run must not solve or simulate")

    monkeypatch.setattr(ota_design, "design_ota_batch", boom)
    monkeypatch.setattr(FLTrainer, "run", boom)
    rs2 = execute(sweep, out_dir=out)
    assert rs2.all_cached
    assert [c.payload["logs"][0]["loss_mean"] for c in rs2] == \
           [c.payload["logs"][0]["loss_mean"] for c in rs1]

    # spec change -> new cell hashes -> cache miss (and with the trainer
    # stubbed out, the miss is observable as the AssertionError)
    changed = SweepSpec(name="cache", base=base.override("run.seed", 99),
                        axes={"design.omega_bias_scale": (1.0, 2.0)})
    with pytest.raises(AssertionError):
        execute(changed, out_dir=out)


def test_interrupted_sweep_persists_finished_cells(tmp_path, monkeypatch):
    """Cells are written the moment they complete: a sweep that dies
    mid-grid resumes from the finished cells, not from scratch."""
    import importlib
    ex = importlib.import_module("repro.api.execute")   # the module (the
    # package attribute `repro.api.execute` is the function, which shadows)
    real = ex._run_cell

    def flaky(cell, ctx):
        if cell.index == 1:
            raise RuntimeError("mid-sweep crash")
        return real(cell, ctx)

    monkeypatch.setattr(ex, "_run_cell", flaky)
    sweep = SweepSpec(name="resume", base=_tiny_scenario(),
                      axes={"design.omega_bias_scale": (1.0, 2.0)})
    with pytest.raises(RuntimeError, match="mid-sweep crash"):
        execute(sweep, out_dir=tmp_path / "rs")

    monkeypatch.setattr(ex, "_run_cell", real)
    rs = execute(sweep, out_dir=tmp_path / "rs")
    assert [c.status for c in rs] == ["cached", "computed"]


def test_partial_cache_recomputes_only_missing(tmp_path):
    base = _tiny_scenario()
    one = SweepSpec(name="grow", base=base,
                    axes={"design.omega_bias_scale": (1.0,)})
    two = SweepSpec(name="grow", base=base,
                    axes={"design.omega_bias_scale": (1.0, 2.0)})
    out = tmp_path / "rs"
    execute(one, out_dir=out)
    rs = execute(two, out_dir=out)     # half-finished sweep: cell 0 cached
    assert [c.status for c in rs] == ["cached", "computed"]


# ---------------------------------------------------------- legacy parity

def test_sweep_reproduces_legacy_fig2_pipeline(tmp_path):
    """A 2-point omega sweep through ``execute()`` matches the legacy
    hand-rolled fig2_ota_sc pipeline (pre-refactor shape: make_sc_setup ->
    batched design -> suite -> run_tuned) per scheme, seed-for-seed."""
    from benchmarks.common import make_sc_setup, run_tuned
    from repro.core import baselines as B
    from repro.core.bounds import ObjectiveWeights

    n, rounds, trials, eval_every = N_DEVICES, 6, 2, 3
    etas = (1.0, 0.25)
    scales = (1.0, 4.0)

    # -- legacy path: one hand-rolled pipeline per omega_bias scale
    legacy = []
    task, ds, dep, eta_max = make_sc_setup(n, samples_per_device=60,
                                           n_train_per_class=60)
    for scale in scales:
        w = ObjectiveWeights.strongly_convex(eta=eta_max, mu=task.mu,
                                             kappa_sc=3.0, n=n)
        w = ObjectiveWeights(omega_var=w.omega_var,
                             omega_bias=w.omega_bias * scale)
        dspec = ota_design.OTADesignSpec(
            lambdas=dep.lambdas, dim=task.dim, g_max=task.g_max,
            e_s=dep.cfg.energy_per_symbol, n0=dep.cfg.noise_power,
            weights=w)
        params, _ = ota_design.design_ota_batch([dspec])
        cell_logs = {}
        for key, agg in (("ideal", B.IdealFedAvg()),
                         ("proposed_ota", B.ProposedOTA(params[0])),
                         ("vanilla_ota", B.VanillaOTA(
                             task.dim, task.g_max,
                             dep.cfg.energy_per_symbol,
                             dep.cfg.noise_power))):
            log, best_eta = run_tuned(task, ds, dep, agg, eta_max=eta_max,
                                      rounds=rounds, trials=trials,
                                      eval_every=eval_every, etas=etas,
                                      backend="numpy")
            cell_logs[key] = (log, best_eta)
        legacy.append(cell_logs)

    # -- declarative path: the same protocol as a 2-point sweep
    base = _tiny_scenario(
        name="fig2_mini",
        # exactly make_sc_setup's data protocol (incl. its 200-per-class
        # test split; _tiny_scenario shrinks it for the other tests)
        data=DataSpec(n_train_per_class=60, n_test_per_class=200,
                      samples_per_device=60),
        run=RunSpec(rounds=rounds, trials=trials, eval_every=eval_every,
                    etas=etas, backend="numpy"),
        schemes=("ideal", "proposed_ota", "vanilla_ota"))
    sweep = SweepSpec(name="fig2_mini", base=base,
                      axes={"design.omega_bias_scale": scales})
    rs = execute(sweep, out_dir=tmp_path / "rs")

    assert len(rs) == len(scales)
    for cell, cell_logs in zip(rs, legacy):
        for rec in cell.payload["logs"]:
            log, best_eta = cell_logs[rec["scheme_key"]]
            assert rec["eta"] == pytest.approx(best_eta, rel=1e-12)
            np.testing.assert_allclose(rec["loss_mean"],
                                       log.global_loss.mean(0), rtol=1e-5)
            np.testing.assert_allclose(rec["acc_mean"],
                                       log.accuracy.mean(0), rtol=1e-5)
            np.testing.assert_allclose(rec["wall_time_s"],
                                       np.asarray(log.wall_time_s),
                                       rtol=1e-5, atol=1e-12)


# --------------------------------------------------------- strict encoder

def test_strict_encoder_handles_numpy_and_raises_on_unknown():
    payload = {"i": np.int64(3), "f": np.float32(1.5), "b": np.bool_(True),
               "a": np.arange(3), "nested": {"x": np.float64(2.0)}}
    out = json.loads(dump_json(payload))
    assert out == {"i": 3, "f": 1.5, "b": True, "a": [0, 1, 2],
                   "nested": {"x": 2.0}}
    assert isinstance(out["b"], bool)      # default=float coerced to 1.0

    class Opaque:
        def __float__(self):               # float()-coercible on purpose:
            return 0.0                     # the legacy encoder ate these

    with pytest.raises(TypeError, match="Opaque"):
        dump_json({"bad": Opaque()})
    with pytest.raises(TypeError):
        dump_json({"cfg": WirelessConfig()})


def test_save_result_stamps_schema_version(tmp_path, monkeypatch):
    import benchmarks.common as common
    monkeypatch.setattr(common, "RESULTS_DIR", tmp_path)
    common.save_result("x", {"v": np.float64(1.0)})
    saved = json.loads((tmp_path / "x.json").read_text())
    assert saved["schema_version"] == SCHEMA_VERSION
    assert saved["v"] == 1.0


def test_cell_payloads_are_schema_versioned(tmp_path):
    rs = execute(_tiny_scenario(), out_dir=tmp_path / "rs")
    payload = rs.cell(0).payload
    assert payload["schema_version"] == SCHEMA_VERSION
    assert payload["kind"] == "scenario_cell"
    on_disk = json.loads(rs.cell(0).path.read_text())
    assert on_disk == json.loads(dump_json(payload))   # tuples -> lists


def test_benchmark_runner_exits_nonzero_on_failed_suite(monkeypatch, capsys):
    """A suite that raises prints its ERROR row, the other suites still
    run, and the harness exits 1 instead of reporting success."""
    import sys
    import types

    import benchmarks.run as runner

    def boom(quick):
        raise RuntimeError("suite broke")

    suites = {"good": types.SimpleNamespace(
                  run=lambda quick: ([("good/x", 1.0, "d")], {})),
              "bad": types.SimpleNamespace(run=boom)}
    monkeypatch.setattr(runner, "_registry", lambda: suites)
    monkeypatch.setattr(runner.compile_cache, "enable", lambda: "")
    monkeypatch.setattr(sys, "argv", ["benchmarks.run"])
    with pytest.raises(SystemExit) as exc:
        runner.main()
    assert exc.value.code == 1
    out = capsys.readouterr().out
    assert "bad,0,ERROR:RuntimeError:suite broke" in out
    assert "good/TOTAL" in out
