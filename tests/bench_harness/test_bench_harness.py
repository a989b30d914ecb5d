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
#: The payload OTA cell, built and read on the chip but not yet measured in
#: full sets, so not in BENCHMARK.json; the tests run it all the same.
PAYLOAD_ENTRIES = Path(__file__).with_name("payload_entries.json")


def _with_payload(root: Path) -> None:
    """Add the unlisted payload cell to the ``BENCHMARK.json`` at ``root``."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    for key, entries in json.loads(PAYLOAD_ENTRIES.read_text()).items():
        names = {e["name"] for e in spec[key]}
        spec[key] += [e for e in entries if e["name"] not in names]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


@pytest.fixture(scope="module")
def full_reg(tmp_path_factory):
    """The benchmark's files with every payload cell listed."""
    root = tmp_path_factory.mktemp("spec")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    _with_payload(root)
    (root / "bench").symlink_to(ROOT / "bench")
    return Registry(root)


# ------------------------------------------------------------ registry

def test_every_cell_resolves_by_name(full_reg):
    reg = full_reg
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


def test_payload_gradient_flops(full_reg):
    reg = full_reg
    b = reg.module("configs", "fedavg_cnn_uplink")
    assert b.grad_flops_per_round(reg.config("fedavg_cnn_uplink"), {}) == \
        4 * 1_663_370 * 100


def test_payload_width_is_the_sourced_cnn(full_reg):
    """d is the parameter count of FedAvg's MNIST CNN, worked out from its
    layers; the cohort is Table 1's K = 100 clients at C = 1.0."""
    cfg = full_reg.config("fedavg_cnn_uplink")
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
    # N=100, d=1,663,370 padded to 26 blocks of 512 x 128 (13,312 rows of
    # 128) a device, 8-bit codes
    per_dev = 13_312
    assert per_dev * 128 == -(-1_663_370 // (512 * 128)) * 512 * 128
    rows = 100 * per_dev
    pack = reg.module("kernels", "quantize_pack_rows_2d")
    ops = [_a("f32", (100, 1, 2), True), _a("f32", (rows, 128)),
           _a("f32", (rows, 128))]
    res = [_a("u32", (rows // 4, 128))]
    flops, nbytes = pack.cost(ops, res)
    assert flops == 10 * rows * 128
    assert nbytes == 2 * rows * 128 * 4 + rows // 4 * 128 * 4
    wsum = reg.module("kernels", "packed_weighted_sum_2d")
    ops = [_a("f32", (100, 1, 3), True), _a("u32", (rows // 4, 128))]
    res = [_a("f32", (per_dev, 128), True)]
    flops, nbytes = wsum.cost(ops, res)
    assert flops == 7 * 100 * per_dev * 128
    assert nbytes == rows // 4 * 128 * 4


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
    _with_payload(root)
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
