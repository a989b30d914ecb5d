"""Compiles for a described TPU v5e: the uplink kernels and two engine scans.

Nothing runs. Each test compiles for one chip of a described ``v5e:2x2``
topology, which catches what interpret mode cannot: blocks not aligned to
the (8, 128) tiling, scalars outside SMEM, casts Mosaic lacks, tiles that
overflow VMEM, 64-bit operands. The topology is described inside a fixture
and only there, so that one test worker alone loads the TPU compiler.
"""
import functools
import math
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.dithered_quant import (LANES, dithered_quantize_2d,
                                          dithered_quantize_rows_2d)
from repro.kernels.ota_combine import ota_combine_2d
from repro.kernels.payload import packed_weighted_sum_2d, quantize_pack_rows_2d
from repro.kernels.row_reduce import row_maxabs_sumsq_2d

#: (devices, payload dimension): Fig. 2 OTA width, the chip smoke test's
#: payload and the benchmark's payload cell (FedAvg's MNIST CNN, K = 100)
WIDTHS = {"fig2": (50, 7850), "payload": (256, 10 ** 6),
          "cell": (100, 1_663_370)}
KERNELS = ("ota_combine", "dithered_quantize", "dithered_quantize_rows",
           "row_maxabs_sumsq", "quantize_pack_4", "quantize_pack_8",
           "quantize_pack_16")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _tile(d, dtype):
    """The row tile the wrappers launch at for a d-wide payload."""
    return ops._block_rows(d, dtype)


def _device_rows(d, block_rows):
    rows = -(-d // LANES)
    return -(-rows // block_rows) * block_rows


def _words(n, d, code_bits):
    """The pack kernels' column block and (N, W) word shape at width d."""
    bc = ops._block_cols(d, code_bits)
    return bc, (n, -(-d // bc) * bc * code_bits // 32)


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kernel", KERNELS)
def test_uplink_kernel_compiles(one_chip, kernel, dtype, width):
    n, d = WIDTHS[width]
    f32 = jnp.float32
    if kernel.startswith("quantize_pack"):
        # the (N, d) gradients and dither as the producers leave them
        code_bits = int(kernel.rsplit("_", 1)[1])
        bc, _ = _words(n, d, code_bits)
        fn = functools.partial(quantize_pack_rows_2d, code_bits=code_bits,
                               block_cols=bc)
        shapes = (((n, d), dtype), ((n, d), f32), ((n, 2), f32))
    elif kernel == "ota_combine":
        # the PS epilogue runs on the aggregated (d,) vector
        br = _tile(d, dtype)
        r = _device_rows(d, br)
        fn = functools.partial(ota_combine_2d, block_rows=br, acc_dtype=f32)
        shapes = (((r, LANES), dtype), ((r, LANES), f32), ((), f32))
    elif kernel == "dithered_quantize":
        br = _tile(d, dtype)
        r = _device_rows(d, br)
        fn = functools.partial(dithered_quantize_2d, block_rows=br)
        shapes = (((r, LANES), dtype), ((r, LANES), f32), ((), f32),
                  ((), f32))
    elif kernel == "dithered_quantize_rows":
        br = _tile(d, dtype)
        r = _device_rows(d, br)
        fn = functools.partial(dithered_quantize_rows_2d, block_rows=br)
        shapes = (((n * r, LANES), dtype), ((n * r, LANES), f32),
                  ((n, 2), f32))
    else:
        br = _tile(d, dtype)
        r = _device_rows(d, br)
        fn = functools.partial(row_maxabs_sumsq_2d, n_dev=n, block_rows=br)
        shapes = (((n * r, LANES), dtype),)
    text = _compile(fn, one_chip, *shapes).as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("code_bits", [4, 8, 16])
def test_packed_weighted_sum_tpu_launch_compiles(one_chip, code_bits, width):
    """The fused sum's one launch, over the (N, W) words, which CPU tests
    run only in interpret mode."""
    n, d = WIDTHS[width]
    bc, words = _words(n, d, code_bits)
    fn = functools.partial(packed_weighted_sum_2d, code_bits=code_bits,
                           d=d, block_cols=bc)
    text = _compile(fn, one_chip, (words, jnp.uint32),
                    ((n, 3), jnp.float32)).as_text()
    assert "tpu_custom_call" in text


#: ``%name[.k] = <type>[dims]{layout} opcode(`` of an HLO instruction
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.-]+) = \w+\[([\d,]*)\]\S* "
                    r"([\w-]+)\(")
_MOVES = ("pad", "copy", "reshape", "transpose", "squeeze")


def _size(dims: str) -> int:
    return math.prod(int(x) for x in dims.split(",") if x)


def _kernels_and_moves(text: str, big: int):
    """The Pallas kernels of an HLO text by name, and its pads, copies,
    reshapes and transposes of arrays of ``big`` elements or more."""
    kernels, moves = [], []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        name, dims, opcode = m.groups()
        if "tpu_custom_call" in line:
            kernels.append(re.sub(r"\.\d+$", "", name))
        elif _size(dims) >= big and (opcode in _MOVES
                                     or name.startswith(_MOVES)):
            moves.append(line.strip()[:160])
    return kernels, moves


def test_fused_sum_reads_the_gradients_in_place(one_chip, monkeypatch):
    """The payload cell's uplink, vmapped over a trial axis of 1 as the
    engine's scan is: the two kernels read the (N, d) arrays their
    producers write, with no pad, reshape or copy of an (N, d)-sized array
    in between, and keep the names the benchmark keys their rooflines by.

    The HLO names a custom call after the last step of its call site's
    name stack: a per-trial program (the engine's scan, here a jitted
    step) leaves the kernel's own name, where a launch straight under
    ``vmap`` would read ``vmap_jit_<name>_``."""
    n, d = WIDTHS["cell"]
    monkeypatch.setattr(ops, "_on_cpu", lambda: False)

    def trial(w, centers, levels, weights):
        # the (N, d) operands made inside the program, as the engine's
        # gradients and dither are
        g = w[None, :] - centers[:, None]
        u = jnp.abs(jnp.sin(g))
        return ops.quantized_weighted_sum(g, levels, u, weights, r_max=8)

    fn = jax.vmap(jax.jit(trial), in_axes=(0, None, None, None))
    f32 = jnp.float32
    text = _compile(fn, one_chip, ((1, d), f32), ((n,), f32), ((n,), f32),
                    ((n,), f32)).as_text()
    kernels, moves = _kernels_and_moves(text, n * d)
    assert sorted(kernels) == ["packed_weighted_sum_2d",
                               "quantize_pack_rows_2d"]
    assert not moves


def _scan_text(engine, aggregator, sharding, monkeypatch, **run):
    """The compiled HLO text of one engine scan for a described chip."""
    _, runner, args = engine.prepare(aggregator, seed=0, **run)
    # the kernels take their TPU branch (no interpret mode) only where
    # JAX's default backend is not the CPU, which it is here
    monkeypatch.setattr(ops, "_on_cpu", lambda: False)
    lowered = runner.lower(*[jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                  sharding=sharding)
                             for a in args])
    return lowered, lowered.compile().as_text()


def test_payload_scan_keeps_the_gradients_dense(one_chip, monkeypatch):
    """The payload cell's scan (FedAvg's MNIST CNN width, N=100, best
    channel with every device, 8-bit codes): no (N, d)-sized array lies in
    a one-sublane (1, 128) tiling, which takes 8x the vector work to write
    or read, and none is padded, copied or reshaped for the kernels. The
    clipped gradients feed both the quantizer scale and the pack kernel;
    left free, XLA has recomputed them for the kernel and tiled the scale's
    operand (1, 128)."""
    from repro.core import baselines as B
    from repro.core.channel import WirelessConfig, make_deployment
    from repro.data.loader import FLDataset
    from repro.fl.engine import FLEngine
    from repro.fl.tasks import SyntheticHighDimTask

    n, d = WIDTHS["cell"]
    task = SyntheticHighDimTask(d, g_max=2 * d ** 0.5, seed=0)
    xs = np.arange(n, dtype=np.float32).reshape(n, 1, 1)
    ds = FLDataset.from_shards([(xs[m], np.zeros(1, np.int32))
                                for m in range(n)], xs[0], np.zeros(1))
    dep = make_deployment(WirelessConfig(n_devices=n, seed=1))
    cfg = dep.cfg
    agg = B.BestChannel(dep, d, task.g_max, cfg.energy_per_symbol,
                        cfg.noise_power, cfg.bandwidth_hz, k=n, r_bits=8)
    eng = FLEngine(task, ds, dep, eta=0.25)
    _, text = _scan_text(eng, agg, one_chip, monkeypatch, rounds=2,
                         trials=1, eval_every=2)
    kernels, moves = _kernels_and_moves(text, n * d)
    sparse = []
    for line in text.splitlines():
        if not re.match(r"^\s*(?:ROOT )?%\S+ = ", line):
            continue
        # the result types: up to the opcode, one or a tuple of them
        head = line.split(" = ", 1)[1]
        result = head[:re.search(r"\s[a-z][\w-]*\(", head).start()]
        if any(_size(dims) >= n * d for dims in
               re.findall(r"\[([\d,]*)\]\{[^}]*T\(1,128\)", result)):
            sparse.append(line.strip()[:160])
    assert sorted(kernels) == ["packed_weighted_sum_2d",
                               "quantize_pack_rows_2d"]
    assert not sparse
    assert not moves


def test_engine_scan_compiles(one_chip, monkeypatch):
    """One engine scan at Fig. 2 width (N=50, d=7850) for proposed digital:
    its uplink runs through the Pallas kernels, and nothing in it is
    64-bit."""
    from repro.core import baselines as B
    from repro.core.channel import WirelessConfig, make_deployment
    from repro.core.digital import DigitalParams
    from repro.data.loader import FLDataset
    from repro.fl.engine import FLEngine
    from repro.fl.tasks import SoftmaxRegressionTask

    n = 50
    task = SoftmaxRegressionTask(n_features=784)
    rng = np.random.default_rng(0)
    # the scan's shapes depend on the width, not the sample count
    shards = [(rng.normal(size=(20, 784)).astype(np.float32),
               rng.integers(0, 10, 20)) for _ in range(n)]
    ds = FLDataset.from_shards(shards, shards[0][0], shards[0][1])
    dep = make_deployment(WirelessConfig(n_devices=n, seed=1))
    cfg = dep.cfg
    params = DigitalParams(
        rhos=0.5 * np.sqrt(dep.lambdas), nus=np.full(n, 0.78),
        r_bits=np.full(n, 4), g_max=task.g_max, dim=task.dim,
        energy_per_symbol=cfg.energy_per_symbol, noise_psd=cfg.noise_power,
        bandwidth_hz=cfg.bandwidth_hz)
    eng = FLEngine(task, ds, dep, eta=0.25)
    lowered, text = _scan_text(eng, B.ProposedDigital(params), one_chip,
                               monkeypatch, rounds=20, trials=2,
                               eval_every=10)
    assert "f64" not in lowered.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert calls
    assert not any("f64" in ln for ln in calls)
