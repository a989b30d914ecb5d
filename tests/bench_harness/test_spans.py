"""CPU tests of the engine's spans and scopes and of their readers.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q tests/bench_harness

They cover the spans a traced ``FLEngine.run`` records and the counts they
carry, the scopes its compiled scan holds, the map from HLO instructions
to scopes, and every reader of ``bench/spans.py`` on a trace recorded on a
TPU v5e (``bench/testdata/record_spans.py``) against numbers worked by
hand, and on a trace of an engine without spans, where each reads None.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spans, trace as tr  # noqa: E402
from bench.metrics import Context  # noqa: E402
from bench.registry import Registry  # noqa: E402
from bench.run import CALL_SPAN  # noqa: E402

TESTDATA = ROOT / "bench" / "testdata"
FIXTURE = TESTDATA / "fig2_spans"
HOST = ("prepare_ms_per_call", "replay_ms_per_call", "dispatch_ms_per_call",
        "evaluate_ms_per_call", "fetch_ms_per_call")
DEVICE = ("grads_ms_per_round", "uplink_ms_per_round", "update_ms_per_round")
NEW = HOST + ("h2d_mb_per_call",) + DEVICE + ("streams_ms_per_round",)


def _fig2(n=4, **engine_kw):
    """A Fig. 2-shaped engine (softmax regression, d=7850) at N=``n``
    and the proposed OTA aggregator, from the benchmark's configuration."""
    from repro.core import baselines as B
    from repro.core.ota import OTAParams

    from repro.core.channel import WirelessConfig, make_deployment
    from repro.data.loader import FLDataset
    from repro.fl.engine import FLEngine
    from repro.fl.tasks import SoftmaxRegressionTask

    from bench import data

    config = Registry().config("fig2_mnist_ota")
    config["data"].update(n_train_per_class=40, n_test_per_class=10,
                          samples_per_device=20)
    xs, ys, x_te, y_te = data.federated_dataset(config["data"], n)
    ds = FLDataset.from_shards([(xs[m], ys[m]) for m in range(n)],
                               x_te, y_te)
    task = SoftmaxRegressionTask(n_features=784, n_classes=10, mu=0.01,
                                 g_max=20.0)
    dep = make_deployment(WirelessConfig(n_devices=n, seed=1))
    des = config["design"]
    agg = B.ProposedOTA(OTAParams(
        gammas=np.asarray(des["gammas"][:n]), alpha=des["alpha"],
        g_max=20.0, dim=task.dim,
        energy_per_symbol=dep.cfg.energy_per_symbol,
        noise_psd=dep.cfg.noise_power))
    return FLEngine(task, ds, dep, 0.25, **engine_kw), agg, dep


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


# ------------------------------------------------------ the engine's spans

@pytest.mark.parametrize("rng", ["replay", "fast"])
def test_a_traced_run_has_nested_spans_with_their_counts(rng):
    eng, agg, _ = _fig2()
    kw = dict(rounds=6, trials=2, eval_every=3, rng=rng)
    eng.run(agg, seed=1, **kw)
    got = spans.traced(lambda: eng.run(agg, seed=2, **kw))
    by = {}
    for s in got:
        by.setdefault(s[0], []).append(s)
    leaves = ["fl.prepare", "fl.dispatch", "fl.evaluate", "fl.fetch"]
    want = ["fl.run"] + leaves + (["fl.replay"] if rng == "replay" else [])
    assert sorted(by) == sorted(want)
    assert all(len(v) == 1 for v in by.values())
    one = {k: v[0] for k, v in by.items()}
    run = one["fl.run"]
    for name in leaves:                    # in order, inside fl.run
        assert _inside(one[name], run)
    assert [one[n][1] for n in leaves] == sorted(one[n][1] for n in leaves)
    assert all(one[a][2] <= one[b][1] for a, b in zip(leaves, leaves[1:]))
    if rng == "replay":
        assert _inside(one["fl.replay"], one["fl.prepare"])
        assert one["fl.replay"][3] == {}
    assert run[3] == {}
    _, _, args = eng.prepare(agg, seed=2, **kw)
    host = [a for a in args if isinstance(a, np.ndarray)]
    assert one["fl.dispatch"][3] == {"h2d_bytes": sum(a.nbytes
                                                      for a in host)}
    data = (eng.x_all, eng.y_all, eng.x_test, eng.y_test)
    assert one["fl.evaluate"][3] == {"h2d_bytes": sum(a.nbytes
                                                      for a in data)}
    assert one["fl.prepare"][3] == {} and one["fl.fetch"][3] == {}


def test_call_stats_come_from_one_traced_call_of_the_cell():
    from bench import fl

    eng, agg, _ = _fig2()
    kw = dict(rounds=6, trials=2, eval_every=3)
    cell = fl.Cell(eng, agg, kw, base_seed=2 ** 31 + 3, keep=0)

    class Ctx:
        pass
    ctx = Ctx()
    ctx.cell = cell
    stats = spans.call_stats(ctx)
    _, _, args = eng.prepare(agg, seed=0, **kw)
    assert stats["fl.dispatch"]["h2d_bytes"] == sum(
        a.nbytes for a in args if isinstance(a, np.ndarray))
    assert spans.call_stats(ctx) is stats          # one call per context
    assert not cell._ws                            # the states were taken


# ------------------------------------------------------ the scan's scopes

SCOPES = ("fl.grads", "fl.streams", "fl.bias", "fl.uplink", "fl.update")


@pytest.fixture(scope="module")
def every_scope_hlo():
    """A scan on the CPU that runs every scoped part of the step: fast
    streams, mini-batches, participation, faults and a digital uplink
    with dither."""
    from repro.core import baselines as B
    from repro.core.faults import FaultSpec

    eng, _, dep = _fig2(batch_size=5, clients_per_round=2,
                        fault=FaultSpec(dropout_prob=0.1))
    cfg = dep.cfg
    agg = B.BestChannel(dep, eng.task.dim, eng.task.g_max,
                        cfg.energy_per_symbol, cfg.noise_power,
                        cfg.bandwidth_hz, k=2, r_bits=4)
    _, runner, args = eng.prepare(agg, rounds=2, trials=1, eval_every=2,
                                  seed=0, rng="fast")
    return runner.lower(*args).compile().as_text()


@pytest.mark.parametrize("scope", SCOPES)
def test_the_compiled_scan_holds_every_scope(every_scope_hlo, scope):
    assert f"/{scope}/" in every_scope_hlo
    assert scope in spans.scopes(every_scope_hlo).values()


HLO = """\
HloModule jit_traced

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %scatter.1 = f32[4]{0} scatter(%param_0, %param_0), to_apply=%add
}

%fused_computation.2 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  %neg.1 = f32[4]{0} negate(%param_0.1), metadata={op_name="jit(f)/while/body/fl.streams/neg"}
  ROOT %exp.1 = f32[4]{0} exponential(%neg.1), metadata={op_name="jit(f)/while/body/fl.uplink/jit(k)/exp"}
}

%fused_computation.3 (param_0.2: f32[4]) -> f32[4] {
  %param_0.2 = f32[4]{0} parameter(0)
  %neg.2 = f32[4]{0} negate(%param_0.2), metadata={op_name="jit(f)/while/body/fl.streams/neg"}
  ROOT %copy.2 = f32[4]{0} copy(%neg.2)
}

%fused_computation.4 (param_0.3: f32[4]) -> f32[4] {
  %param_0.3 = f32[4]{0} parameter(0)
  ROOT %fusion.11 = f32[4]{0} fusion(%param_0.3), kind=kLoop, calls=%fused_computation.2
}

ENTRY %main.9 (Arg_0.1: f32[4]) -> f32[4] {
  %Arg_0.1 = f32[4]{0} parameter(0)
  %dot.2 = f32[4]{0} multiply(%Arg_0.1, %Arg_0.1), metadata={op_name="jit(f)/while/body/closed_call/fl.grads/vmap(transpose(jvp()))/mul"}
  %copy.3 = f32[4]{0} copy(%dot.2)
  %fusion.4 = f32[4]{0} fusion(%copy.3), kind=kCustom, calls=%fused_computation.1
  %fusion.5 = f32[4]{0} fusion(%fusion.4), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(f)/while/body/fl.update/add"}
  %add.6 = f32[4]{0} add(%Arg_0.1, %Arg_0.1), metadata={op_name="jit(f)/while/body/dynamic_slice;fl.update/sub"}
  %fusion.10 = f32[4]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.3
  %fusion.12 = f32[4]{0} fusion(%Arg_0.1), kind=kCustom, calls=%fused_computation.4
  %while.7 = f32[4]{0} while(%dot.2), condition=%cond, body=%body, metadata={op_name="jit(f)/while"}
  ROOT %copy.8 = f32[4]{0} copy(%while.7)
}
"""


#: the instructions that have a scope by their own metadata
OWN = {"dot.2": "fl.grads",          # its own op_name
       "fusion.5": "fl.uplink",      # the root's, not its own
       "fusion.12": "fl.uplink",     # the root's, through a nested fusion
       "add.6": "fl.update"}         # a segment after ';'


def test_scopes_of_instructions_fusions_and_what_the_compiler_adds():
    got = spans.scopes(HLO)
    assert {n: got[n] for n in OWN} == OWN
    assert got["fusion.10"] == "fl.streams"      # unscoped root: what it fuses
    assert got["fusion.4"] == "fl.grads"         # no metadata: the producer
    assert got["copy.3"] == "fl.grads"           # a copy the compiler added
    # the loop, and what reaches a scope only through it, stay unscoped
    assert got["while.7"] is None and got["copy.8"] is None
    assert got["Arg_0.1"] is None


def test_scopes_by_their_own_metadata_alone():
    """Without inference an op the compiler left without metadata, and a
    fusion whose root has none, stay unscoped."""
    got = spans.scopes(HLO, infer=False)
    assert {n: s for n, s in got.items() if s} == dict(
        OWN, **{"neg.1": "fl.streams", "exp.1": "fl.uplink",
                "neg.2": "fl.streams", "fusion.11": "fl.uplink"})


# ------------------------------------------------ readers, on chip traces

def _context(path, cell=None) -> Context:
    return Context(trace=tr.load(str(path)), span_prefix=CALL_SPAN,
                   cell=cell, config_module=None, config={}, traffic={},
                   peaks={}, registry=Registry())


@pytest.fixture(scope="module")
def hand():
    return json.loads(FIXTURE.with_suffix(".hand.json").read_text())


@pytest.fixture
def fixture_ctx(monkeypatch, hand):
    """The recorded trace, with the counts its spans carry and its scan's
    HLO text standing in for the extra call and the compile, and the
    recorded calls' trials x rounds for the cell's."""
    path = FIXTURE.with_suffix(".xplane.pb")
    stats = {n: s for n, _, _, s in spans.host_spans(str(path))}
    monkeypatch.setattr(spans, "call_stats", lambda ctx: stats)
    monkeypatch.setattr(spans, "scan_hlo", lambda cell: FIXTURE.with_suffix(
        ".hlo.txt").read_text())
    return _context(path, SimpleNamespace(
        rounds_per_call=hand["rounds_per_call"]))


def test_each_scan_runs_between_its_dispatch_and_its_fetch(fixture_ctx):
    """The engine's spans and the device's programs share one clock: each
    execution of the scan starts at or after the start of its call's
    ``fl.dispatch`` and ends before that call's ``fl.fetch`` ends."""
    ctx = fixture_ctx
    dispatch = spans.in_window(ctx, "fl.dispatch")
    fetch = spans.in_window(ctx, "fl.fetch")
    scans = tr.merge(tr.clip(tr.scan_intervals(ctx.trace), ctx.lo, ctx.hi))
    assert len(ctx.calls) == len(dispatch) == len(fetch) == len(scans) == 2
    for call, d, f, (s, e) in zip(ctx.calls, dispatch, fetch, scans):
        assert call.start <= d.start <= s and e <= f.end <= call.end


@pytest.mark.parametrize("metric", HOST + ("h2d_mb_per_call",) + DEVICE)
def test_reader_on_the_chip_fixture_gives_the_hand_value(fixture_ctx, hand,
                                                         metric):
    value = Registry().module("metrics", metric).read(fixture_ctx)
    assert value == pytest.approx(hand[metric], rel=1e-9)


def test_the_fixture_has_no_stream_ops(fixture_ctx):
    """Replayed OTA draws nothing in the scan: no ``fl.streams`` op, so
    ``streams_ms_per_round`` is listed for the digital cell only."""
    assert Registry().module("metrics",
                             "streams_ms_per_round").read(fixture_ctx) is None


def test_scope_map_agrees_with_the_table_read_by_hand(hand):
    """Every instruction the fixture's scan ran, scoped as reading its HLO
    by hand gives: its own op_name, its fused root, else its producer; the
    loops and the slicing of the scan's inputs unscoped."""
    got = spans.scopes(FIXTURE.with_suffix(".hlo.txt").read_text())
    assert {n: got[n] for n in hand["scopes"]} == hand["scopes"]


def test_scope_map_by_own_metadata_agrees_with_the_hand_table(hand):
    """Read by its own op_name or its fused root's alone, every op the
    fixture's scan ran is scoped as by hand, the rest unscoped."""
    got = spans.scopes(FIXTURE.with_suffix(".hlo.txt").read_text(),
                       infer=False)
    assert {n: got[n] for n in hand["scopes"]} == hand["scopes_own"]


@pytest.mark.parametrize("infer", [True, False])
def test_scoped_ops_cover_the_fixture_scan(fixture_ctx, hand, infer):
    ctx = fixture_ctx
    secs = spans.scope_seconds(
        ctx.trace, spans.scopes(spans.scan_hlo(None), infer=infer),
        ctx.lo, ctx.hi)
    key = "unscoped_s" if infer else "unscoped_own_s"
    assert secs.get(None, 0.0) == pytest.approx(hand[key], rel=1e-9)
    assert sum(secs.values()) == pytest.approx(hand["scan_op_self_s"],
                                               rel=1e-9)


@pytest.mark.parametrize("metric", NEW)
def test_reader_reads_none_without_the_engines_spans(metric):
    """On a trace of an engine that has no spans (``payload_tiny``, the
    parent of the spans), every new reader reads None and raises nothing."""
    ctx = _context(TESTDATA / "payload_tiny.xplane.pb")
    assert Registry().module("metrics", metric).read(ctx) is None
