"""CPU tests of the chip benchmark's harness (``bench/``).

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q tests/bench_harness

They cover the harness alone: finding files by name, the window's
arithmetic, the FLOP and byte counts, the trace reduction on a trace
recorded on a TPU v5e (``bench/testdata``), the exit without a chip, and
the correctness check with the timed path broken underneath it, at sizes
a CPU holds.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import faults, fl, streams, trace as tr, window  # noqa: E402
from bench.registry import Registry  # noqa: E402

TESTDATA = ROOT / "bench" / "testdata"


# ------------------------------------------------------------ registry

def test_every_cell_resolves_by_name():
    reg = Registry()
    for cell in reg.spec["workloads"]:
        config = reg.config(cell["config"])
        assert config["name"] == cell["config"]
        traffic = reg.traffic(cell["traffic"])
        assert traffic["rounds"] % traffic["eval_every"] == 0
        assert reg.limits(cell["name"])["compare"]
        reg.module("configs", cell["config"])
        reg.module("configs", cell["config"] + "_ref")
        names = {m["name"] for m in reg.per_layer(cell)}
        assert {"scan_ms_per_round", "device_idle_share",
                "host_ms_per_call"} <= names
        # the model step is the work only where a model is trained
        assert ("step_mfu" in names) == (cell["config"] == "fig2_mnist_ota")
        for name in names:
            assert callable(reg.module("metrics", name).read)


def test_a_new_config_traffic_and_metric_are_files_and_entries(tmp_path):
    """A later PR adds a cell by new files and new entries alone."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    new = tmp_path / "bench"
    (new / "configs" / "tiny.json").write_text(json.dumps({"name": "tiny"}))
    (new / "configs" / "tiny.py").write_text("SIZE = 3\n")
    (new / "traffic" / "burst.json").write_text('{"rounds": 4}')
    (new / "limits" / "tiny.burst.json").write_text(
        '{"calls_checked": 1, "compare": {}}')
    (new / "metrics" / "new_share.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    spec["configs"].append({"name": "tiny", "source": "x", "reduced": [],
                            "file": "bench/configs/tiny.json", "why": "x"})
    spec["workloads"].append({"name": "tiny.burst", "config": "tiny",
                              "traffic": "burst", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "new_share", "unit": "%",
                              "better": "higher", "source": "device_trace",
                              "layer": "device",
                              "moves": "device_rounds_per_s",
                              "workloads": ["tiny.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    reg = Registry(tmp_path)
    cell = reg.cell("tiny.burst")
    assert reg.config(cell["config"]) == {"name": "tiny"}
    assert reg.traffic(cell["traffic"]) == {"rounds": 4}
    assert reg.module("configs", "tiny").SIZE == 3
    metrics = {m["name"] for m in reg.per_layer(cell)}
    assert "new_share" in metrics
    assert reg.module("metrics", "new_share").read(None) == 42.0
    # a metric that lists its cells stays out of the others
    assert "new_share" not in {m["name"] for m in
                               reg.per_layer(reg.cell("fig2_ota.full"))}


def test_unknown_names_and_device_kinds_are_errors():
    reg = Registry()
    with pytest.raises(KeyError):
        reg.cell("no_such.cell")
    with pytest.raises(FileNotFoundError):
        reg.traffic("no_such_traffic")
    with pytest.raises(KeyError):
        reg.peaks("TPU v99")
    assert reg.peaks("TPU v5 lite") == {"bf16_flops_per_s": 197e12,
                                        "hbm_bytes_per_s": 819e9}


# -------------------------------------------------------------- window

def test_closed_loop_runs_every_started_call_to_its_end():
    now = [0.0]
    lengths = [0.4, 0.5, 0.3, 0.6, 0.2]

    def clock():
        return now[0]

    def call(i):
        now[0] += lengths[i]

    calls = window.closed_loop(call, 1.0, clock=clock)
    # calls start at 0, 0.4 and 0.9 (< 1.0); the third ends at 1.2
    assert [c.index for c in calls] == [0, 1, 2]
    assert window.window_seconds(calls) == pytest.approx(1.2)
    # three calls of 10 device-rounds over the whole 1.2 s window
    assert window.rate(calls, 10) == pytest.approx(25.0)


def test_p90_is_over_every_call():
    calls = [window.Call(i, 0.0, s) for i, s in
             enumerate([1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0, 9.0, 10.0,
                        11.0])]
    # inclusive quantiles of 1..11: the 90th lies at 1 + 0.9 * 10
    assert window.percentile([c.seconds for c in calls], 90) == \
        pytest.approx(10.0)
    assert window.percentile([3.0], 90) == 3.0


def test_call_seeds_are_fixed_by_the_run_seed():
    big = 2 ** 31 + 12345
    seeds = [fl.call_seed(big, i) for i in range(-1, 50)]
    assert seeds == [fl.call_seed(big, i) for i in range(-1, 50)]
    assert len(set(seeds)) == len(seeds)
    assert all(0 <= s < 2 ** 31 for s in seeds)
    assert fl.call_seed(big + 1, 0) != fl.call_seed(big, 0)


# ------------------------------------ the window's sample and its check

class _FakeEngine:
    """Stands in for ``FLEngine``: each call's states are a device array of
    a shape no other test makes, and a weak reference to each is kept."""

    SHAPE = (2, 3, 17)

    def __init__(self, base_seed):
        import jax
        self.jax = jax
        self.dep = argparse.Namespace(n_devices=4)
        self.index = {fl.call_seed(base_seed, i): i for i in range(-1, 64)}
        self.states: list = []        # weakref of call i's states
        self.inside: list = []        # live earlier states during call i

    def _evaluate(self, ws):
        return None

    def _alive(self, before: int) -> list:
        return [j for j, r in enumerate(self.states[:before])
                if r() is not None]

    def run(self, aggregator, *, seed, **kw):
        import weakref
        i = self.index[seed]
        ws = self.jax.numpy.full(self.SHAPE, float(i), np.float32)
        self.states.append(weakref.ref(ws))
        self._evaluate(ws)
        self.inside.append(self._alive(max(i - 1, 0)))
        z = np.zeros((2, 3))
        return argparse.Namespace(global_loss=z, accuracy=z,
                                  wall_time_s=z[0])


def _reservoir_after_the_call(base_seed: int, keep: int, calls: int) -> list:
    """The calls sampled when the draw is taken after each call returns."""
    pick = np.random.default_rng((int(base_seed), 7))
    kept: list = []
    for index in range(calls):
        if len(kept) < keep:
            kept.append(index)
        else:
            j = int(pick.integers(0, index + 1))
            if j < keep:
                kept[j] = index
    return kept


@pytest.mark.parametrize("base_seed", (3, 2 ** 31 + 11, 4_000_000_007))
def test_kept_states_leave_the_chip(base_seed):
    """No call's states outlive the next call on the device; the kept ones
    are host arrays; the sample is the one a draw after the call took."""
    import jax
    eng = _FakeEngine(base_seed)
    cell = fl.Cell(eng, None, {"trials": 2, "rounds": 2, "eval_every": 1},
                   base_seed, keep=3)
    def live_calls():                         # the call a state is of
        return {int(a.ravel()[0]) for a in jax.live_arrays()
                if a.shape == eng.SHAPE}

    for i in range(10):
        cell.call(i)
        assert eng._alive(i) == [], i         # all but this call's: gone
        assert live_calls() <= {i}
        assert eng.inside[i] == [], i         # none before the previous
    cell.settle()
    assert eng._alive(10) == []
    assert live_calls() == set()
    assert [k[0] for k in cell.kept] == \
        _reservoir_after_the_call(base_seed, 3, 10)
    for index, seed, out in cell.kept:
        assert type(out["ws"]) is np.ndarray
        assert out["ws"].dtype == np.float32
        assert (out["ws"] == index).all()
        assert seed == fl.call_seed(base_seed, index)


def _readings_whole(got: dict, want: dict) -> dict:
    """The check's numbers from whole-array float64 copies."""
    ws = np.asarray(got["ws"], np.float64)
    wr = np.asarray(want["ws"], np.float64)
    num = np.linalg.norm(ws[:, 1:] - wr[:, 1:], axis=-1)
    den = np.maximum(np.linalg.norm(wr[:, 1:], axis=-1), 1e-30)
    upd = np.maximum(np.linalg.norm(wr[:, 1:] - wr[:, :1], axis=-1), 1e-30)
    lp, lr = np.asarray(got["loss"]), np.asarray(want["loss"])
    wp, wr_ = np.asarray(got["wall"]), np.asarray(want["wall"])
    live = np.abs(wr_) > 0
    return {
        "state_gap": float(np.max(num / den)),
        "update_gap": float(np.max(num / upd)),
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "acc_gap": float(np.max(np.abs(np.asarray(got["acc"])
                                       - np.asarray(want["acc"])))),
        "wall_gap": float(np.max(np.abs(wp[live] - wr_[live])
                                 / np.abs(wr_[live])) if live.any() else 0.0),
    }


@pytest.mark.parametrize("chunk", (64, 1000, 3000, 1 << 22))
def test_chunked_readings_equal_whole_array_formulas(monkeypatch, chunk):
    """Chunks of columns (64), one row (1000) or several rows (3000) at a
    time give the whole-array float64 numbers."""
    monkeypatch.setattr(fl, "CHUNK", chunk)
    rng = np.random.default_rng(chunk)
    trials, E, d = 3, 7, 1000
    wr = rng.standard_normal((trials, E, d)).astype(np.float32)
    ws = (wr + 1e-3 * rng.standard_normal(wr.shape)).astype(np.float32)
    loss = rng.random((trials, E)) + 0.5
    wall = np.concatenate([[0.0], rng.random(E - 1) + 0.1])
    got = {"ws": ws, "loss": loss * (1 + 1e-4 * rng.random((trials, E))),
           "acc": rng.random((trials, E)), "wall": wall * 1.001}
    want = {"ws": wr, "loss": loss, "acc": rng.random((trials, E)),
            "wall": wall}
    r, whole = fl.readings(got, want), _readings_whole(got, want)
    assert set(r) == set(whole)
    for k, v in whole.items():
        assert v > 0
        assert r[k] == pytest.approx(v, rel=1e-12, abs=0), k


def test_update_gap_against_a_hand_count():
    # the reference moves w0 = (1, 0) by (3, 4), then by (6, 8) in all
    want = {"ws": np.array([[[1, 0], [4, 4], [7, 8]]], np.float32),
            "loss": np.ones((1, 3)), "acc": np.zeros((1, 3)),
            "wall": np.zeros(3)}
    # the program is 0.5 off at both eval points; its w0 does not count
    got = dict(want, ws=np.array([[[9, 9], [4.5, 4], [7, 8.5]]],
                                 np.float32))
    r = fl.readings(got, want)
    assert r["update_gap"] == pytest.approx(max(0.5 / 5, 0.5 / 10),
                                            rel=1e-12)
    assert r["state_gap"] == pytest.approx(0.5 / np.sqrt(32), rel=1e-12)
    assert r["loss_gap"] == r["acc_gap"] == r["wall_gap"] == 0.0
    shorter = dict(got, ws=got["ws"][:, :2])
    assert fl.readings(shorter, want)["update_gap"] == np.inf


# -------------------------------------------------------- FLOP counts

def test_softmax_gradient_flops_against_a_hand_count():
    reg = Registry()
    cfg = reg.config("fig2_mnist_ota")
    b = reg.module("configs", "fig2_mnist_ota")
    # logits x W^T and the weight gradient (P - Y)^T x, 2 n f C each
    per_device = 2 * (2 * 1000 * 784 * 10)
    assert b.grad_flops_per_device(cfg) == per_device == 31_360_000
    assert b.grad_flops_per_round(cfg, {}) == 50 * per_device

    import jax
    import jax.numpy as jnp
    from repro.fl.tasks import SoftmaxRegressionTask
    task = SoftmaxRegressionTask(784, 10)
    cost = task.device_grads_fn.lower(
        jnp.zeros(7850, jnp.float32), jnp.zeros((1, 1000, 784), jnp.float32),
        jnp.zeros((1, 1000), jnp.int32)).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    # XLA counts the matmuls plus the softmax and clip: within 1 % above
    assert per_device <= cost["flops"] <= 1.01 * per_device
    del jax


def test_payload_gradient_flops():
    reg = Registry()
    b = reg.module("configs", "fedavg_cnn_uplink")
    assert b.grad_flops_per_round(reg.config("fedavg_cnn_uplink"), {}) == \
        4 * 1_663_370 * 100


def test_payload_width_is_the_sourced_cnn():
    """d is the parameter count of FedAvg's MNIST CNN, worked out from its
    layers; the cohort is Table 1's K = 100 clients at C = 1.0."""
    cfg = Registry().config("fedavg_cnn_uplink")
    conv1 = 5 * 5 * 1 * 32 + 32
    conv2 = 5 * 5 * 32 * 64 + 64
    fc = 7 * 7 * 64 * 512 + 512
    out = 512 * 10 + 10
    assert cfg["task"]["dim"] == cfg["model"]["n_params"] == \
        conv1 + conv2 + fc + out == 1_663_370
    assert cfg["wireless"]["n_devices"] == 100
    assert cfg["task"]["g_max"] == pytest.approx(2 * cfg["task"]["dim"] ** 0.5)


# -------------------------------------------------------- kernel counts

def _a(dtype, shape, vmem=False):
    return tr.Array(dtype, tuple(shape), vmem)


def test_ota_combine_count():
    k = Registry().module("kernels", "ota_combine_2d")
    # fig2: 4 trials x a (64, 128) block; the compiler keeps them in VMEM
    ops = [_a("f32", (1, 1, 1)), _a("f32", (4, 64, 128), True),
           _a("f32", (4, 64, 128), True)]
    res = [_a("f32", (4, 64, 128), True)]
    assert k.cost(ops, res) == (2 * 4 * 64 * 128, 4)
    # the same launch from HBM: two operand blocks and the result
    ops = [_a("f32", (1, 1, 1)), _a("f32", (8192, 128)),
           _a("f32", (8192, 128))]
    res = [_a("f32", (8192, 128))]
    assert k.cost(ops, res) == (2 * 8192 * 128, 4 + 3 * 8192 * 128 * 4)


def test_payload_kernel_counts():
    reg = Registry()
    # N=100, d=1,663,370 read in place; 8-bit codes, 4 a word, in 26
    # column blocks of 65,536: 425,984 words a device
    n, d, words = 100, 1_663_370, 26 * 65_536 // 4
    pack = reg.module("kernels", "quantize_pack_rows_2d")
    ops = [_a("f32", (n, 2), True), _a("f32", (n, d)), _a("f32", (n, d))]
    res = [_a("u32", (n, words))]
    flops, nbytes = pack.cost(ops, res)
    assert flops == 10 * n * d
    assert nbytes == 2 * n * d * 4 + n * words * 4
    # the sum reads the words once; its (1, d) result stays in VMEM
    wsum = reg.module("kernels", "packed_weighted_sum_2d")
    ops = [_a("f32", (n, 3), True), _a("u32", (n, words))]
    res = [_a("f32", (1, d), True)]
    flops, nbytes = wsum.cost(ops, res)
    assert flops == 7 * n * d
    assert nbytes == n * words * 4


def test_kernel_event_parsing():
    name = ('%quantize_pack_rows_2d.11 = u32[524288,128]{1,0:T(8,128)} '
            'custom-call(f32[256,1,2]{2,1,0:T(1,128)S(1)} %bitcast.87, '
            'f32[2097152,128]{1,0:T(8,128)} %squeeze.78, '
            'f32[2097152,128]{1,0:T(8,128)} %squeeze.79), '
            'custom_call_target="tpu_custom_call", operand_layout_'
            'constraints={f32[256,1,2]{2,1,0}}')
    t = tr.Trace(modules=[], host=[],
                 ops=[tr.Event(name, 1000.0, 3_612_665.0)])
    (k, launches), = tr.kernel_events(t).items()
    assert k == "quantize_pack_rows_2d"
    seconds, ops, res = launches[0]
    assert seconds == pytest.approx(3.611665e-3)
    assert ops == [_a("f32", (256, 1, 2), True), _a("f32", (2097152, 128)),
                   _a("f32", (2097152, 128))]
    assert res == [_a("u32", (524288, 128))]


# --------------------------------------------------------------- trace

def test_interval_arithmetic():
    iv = [(0, 10), (5, 20), (30, 40), (35, 36)]
    assert tr.merge(iv) == [(0, 20), (30, 40)]
    assert tr.union_length(iv, 0, 100) == 30
    assert tr.union_length(iv, 15, 32) == 7
    assert tr.gaps(iv, 0, 50) == [(20, 30), (40, 50)]
    assert tr.gaps(iv, 10, 35) == [(20, 30)]


def test_self_times_subtract_nested_ops():
    ops = [tr.Event("%while.1 = f32[4]", 0, 100),
           tr.Event("%fusion.2 = f32[8]", 10, 30),
           tr.Event("%fusion.3 = f32[8]", 40, 70),
           tr.Event("%copy.4 = f32[2]", 120, 125)]
    st = tr.self_times(ops, 0, 200)
    assert st["%while.1 = f32[4]"] == pytest.approx(50e-9)
    assert st["%fusion.2 = f32[8]"] == pytest.approx(20e-9)
    assert st["%copy.4 = f32[2]"] == pytest.approx(5e-9)


@pytest.fixture(scope="module")
def chip_trace():
    """Two payload calls (N=4, d=2^17, 2 rounds) traced on a TPU v5e by
    ``bench/testdata/record_trace.py``."""
    return tr.load(str(TESTDATA / "payload_tiny.xplane.pb"))


def test_recorded_trace_against_hand_numbers(chip_trace):
    from bench.run import CALL_SPAN
    hand = json.loads((TESTDATA / "payload_tiny.hand.json").read_text())
    calls = tr.spans(chip_trace, CALL_SPAN)
    assert len(calls) == 2
    lo, hi = calls[0].start, calls[-1].end
    assert (hi - lo) == pytest.approx(hand["window_ns"])
    busy = tr.union_length(tr.busy_intervals(chip_trace), lo, hi)
    assert busy == pytest.approx(hand["busy_ns"])
    assert 1 - busy / (hi - lo) == pytest.approx(hand["idle_share"])
    scan = tr.union_length(tr.scan_intervals(chip_trace), lo, hi)
    assert scan == pytest.approx(hand["scan_ns"])
    kern = tr.kernel_events(chip_trace)
    assert sorted(kern) == sorted(hand["kernel_s"])
    for name, seconds in hand["kernel_s"].items():
        assert len(kern[name]) == hand["launches"]
        assert sum(k[0] for k in kern[name]) == pytest.approx(seconds)


# ----------------------------------------------------------- no chip

def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "fig2_ota.full", "--seed", str(2 ** 31 + 7), "--seconds", "1"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fig2_ota.full",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""


# ------------------------------------------- the check, with faults planted

TINY_FIG2 = {"wireless": {"n_devices": 4},
             "data": {"n_train_per_class": 40, "n_test_per_class": 10,
                      "samples_per_device": 20}}
TINY_TRAFFIC = {"fig2_ota.full": {"rounds": 6, "trials": 2, "eval_every": 3},
                "payload_1m.digital8": {"rounds": 2, "eval_every": 2},
                "payload_1m.ota": {"rounds": 2, "eval_every": 2}}


@functools.lru_cache(maxsize=None)
def _tiny_design() -> str:
    """kappa and the design (15) of the tiny Fig. 2 configuration, solved
    by the program as ``bench/configs/fig2_mnist_ota.py`` does."""
    fig2 = Registry().config("fig2_mnist_ota")
    for block, upd in TINY_FIG2.items():
        fig2[block].update(upd)
    mod = Registry().module("configs", "fig2_mnist_ota")
    return json.dumps(mod.regenerate(fig2))


def _tiny_tree(root: Path, sizes: dict) -> Registry:
    """A copy of the benchmark whose cells run at CPU sizes; the
    configuration modules, references and limits are the real ones."""
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("testdata"))
    b = root / "bench"
    fig2 = json.loads((b / "configs/fig2_mnist_ota.json").read_text())
    if "fig2" in sizes:          # the stored design belongs to these sizes
        fig2["wireless"]["n_devices"] = sizes["fig2"]["n"]
        fig2["data"].update(sizes["fig2"]["data"])
    else:
        for block, upd in TINY_FIG2.items():
            fig2[block].update(upd)
        fig2["design"] = json.loads(_tiny_design())
    (b / "configs/fig2_mnist_ota.json").write_text(json.dumps(fig2))
    pay = json.loads((b / "configs/fedavg_cnn_uplink.json").read_text())
    pay["wireless"]["n_devices"] = 4
    pay["task"]["dim"] = 1 << 17
    (b / "configs/fedavg_cnn_uplink.json").write_text(json.dumps(pay))
    for cell, upd in TINY_TRAFFIC.items():
        name = Registry(root).cell(cell)["traffic"]
        traffic = json.loads((b / f"traffic/{name}.json").read_text())
        traffic.update(upd)
        traffic.update(sizes.get("traffic", {}).get(cell, {}))
        (b / f"traffic/{name}.json").write_text(json.dumps(traffic))
    return Registry(root)


def _run(reg, cell, monkeypatch, seed=2 ** 31 + 11):
    from bench import run as R
    monkeypatch.setattr(R, "require_chips",
                        lambda jax, chips: jax.devices()[:chips])
    # with the variable set the program sets no cache directory of its own,
    # and JAX, which read the variable at import, keeps its cache off:
    # the tests write nothing into the checkout's .jax_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(reg.root / "cache"))
    return R.run(argparse.Namespace(workload=cell, seed=seed, seconds=0.5,
                                    trace=0), reg)


def _break(reg, cell, monkeypatch, how):
    """Replace the cell's program so the timed path is broken underneath."""
    mod = reg.module("configs", reg.cell(cell)["config"])
    monkeypatch.setattr(mod, "program", faults.broken(mod.program, how))


CELLS = ("fig2_ota.full", "payload_1m.digital8", "payload_1m.ota")


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(tmp_path, monkeypatch, cell):
    res = _run(_tiny_tree(tmp_path, {}), cell, monkeypatch)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("how", faults.FAULTS)
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(tmp_path, monkeypatch, cell, how):
    reg = _tiny_tree(tmp_path, {})
    _break(reg, cell, monkeypatch, how)
    res = _run(reg, cell, monkeypatch)
    assert not res["correct"], res["checks"]
    assert res["failed"] >= 1


class _ReferenceAsProgram:
    """The reference put in the program's place, with the engine's call."""

    def __init__(self, reference, n_devices: int):
        self.reference = reference
        self.dep = argparse.Namespace(n_devices=n_devices)

    def _evaluate(self, ws):
        return None

    def run(self, aggregator, *, seed, **kw):
        out = self.reference.run(seed)
        self._evaluate(out["ws"])
        return argparse.Namespace(global_loss=out["loss"],
                                  accuracy=out["acc"],
                                  wall_time_s=out["wall"])


#: Fig. 2 at the cell's own width, depth and rounds (one trial), so that
#: the control's gap is the chip's; a bf16 payload fails at any size
CONTROL_SIZES = {
    "fig2_ota.full": {"fig2": {"n": 50, "data": {
        "n_train_per_class": 6000, "n_test_per_class": 200,
        "samples_per_device": 1000}},
        "traffic": {"fig2_ota.full": {"rounds": 300, "trials": 1}}},
    "payload_1m.digital8": {},
    "payload_1m.ota": {},
}


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(tmp_path, monkeypatch, cell):
    reg = _tiny_tree(tmp_path, CONTROL_SIZES[cell])
    control = reg.limits(cell)["control"]
    name = reg.cell(cell)["config"]
    mod = reg.module("configs", name)
    program = mod.program
    ref_mod = reg.module("configs", name + "_ref")

    def controlled(config, traffic, arrays):
        _, agg, kw = program(config, traffic, arrays)
        ref = ref_mod.Reference(config, traffic,
                                precision=control["precision"])
        return _ReferenceAsProgram(ref, config["wireless"]["n_devices"]), \
            agg, kw
    monkeypatch.setattr(mod, "program", controlled)
    # the stand-in has no compiled scan whose footprint could be read
    monkeypatch.setattr(fl.Cell, "program_bytes", lambda self: 0)
    res = _run(reg, cell, monkeypatch)
    assert not res["correct"], res["checks"]


# ------------------------------------------- the stored design against (15)

def test_stored_design_solves_problem_15():
    reg = Registry()
    ref = reg.module("configs", "fig2_mnist_ota_ref")
    got = ref.check_design(reg.config("fig2_mnist_ota"))
    assert got["objective_gap"] <= ref.DESIGN_RTOL
    assert got["stationarity"] <= ref.DESIGN_STATIONARY
    assert got["gamma_over_max"] <= 1.0


@pytest.mark.parametrize("change", ("gamma", "alpha", "objective", "kappa",
                                    "beyond_max"))
def test_a_design_that_does_not_solve_15_is_refused(change):
    reg = Registry()
    ref = reg.module("configs", "fig2_mnist_ota_ref")
    cfg = reg.config("fig2_mnist_ota")
    des = cfg["design"]
    if change == "gamma":            # off the optimum, alpha kept in step
        des["gammas"][7] *= 1.01
        des["alpha"] = None
    elif change == "alpha":
        des["alpha"] *= 1 + 1e-6
    elif change == "objective":
        des["objective"] *= 1 + 1e-6
    elif change == "kappa":          # the objective of another weighting
        des["kappa"] *= 1.01
    else:                            # past the power-limited maximum
        des["gammas"] = [g * 1e3 for g in des["gammas"]]
    if des["alpha"] is None:         # sum_m gamma_m exp(-c_m gamma_m^2)
        lam, e_s, _ = streams.wireless_constants(cfg["wireless"])
        g = np.asarray(des["gammas"])
        c = cfg["task"]["g_max"] ** 2 / (7850 * lam * e_s)
        des["alpha"] = float(np.sum(g * np.exp(-c * g * g)))
    with pytest.raises(ValueError, match="does not solve"):
        ref.check_design(cfg)
    with pytest.raises(ValueError, match="does not solve"):
        ref.Reference(cfg, reg.traffic("full"))
