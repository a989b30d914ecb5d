"""Compiles for a described TPU v5e: the uplink kernels and one engine scan.

Nothing runs. Each test compiles for one chip of a described ``v5e:2x2``
topology, which catches what interpret mode cannot: blocks not aligned to
the (8, 128) tiling, scalars outside SMEM, casts Mosaic lacks, tiles that
overflow VMEM, 64-bit operands. The topology is described inside a fixture
and only there, so that one test worker alone loads the TPU compiler.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.dithered_quant import (LANES, dithered_quantize_2d,
                                          dithered_quantize_rows_2d)
from repro.kernels.ota_combine import ota_combine_2d
from repro.kernels.payload import (min_block_rows, packed_weighted_sum_2d,
                                   quantize_pack_rows_2d)
from repro.kernels.row_reduce import row_maxabs_sumsq_2d

#: (devices, payload dimension): Fig. 2 OTA width and the payload cell's
WIDTHS = {"fig2": (50, 7850), "payload": (256, 10 ** 6)}
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


def _tile(d, dtype, min_rows=8):
    """The tile the wrappers launch at for a d-wide payload."""
    return ops._block_rows(d, dtype, min_rows=min_rows)


def _device_rows(d, block_rows):
    rows = -(-d // LANES)
    return -(-rows // block_rows) * block_rows


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kernel", KERNELS)
def test_uplink_kernel_compiles(one_chip, kernel, dtype, width):
    n, d = WIDTHS[width]
    f32 = jnp.float32
    if kernel.startswith("quantize_pack"):
        code_bits = int(kernel.rsplit("_", 1)[1])
        br = _tile(d, dtype, min_block_rows(code_bits))
        r = _device_rows(d, br)
        fn = functools.partial(quantize_pack_rows_2d, code_bits=code_bits,
                               block_rows=br)
        shapes = (((n * r, LANES), dtype), ((n * r, LANES), f32),
                  ((n, 2), f32))
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
    """The launch a TPU takes (``dev_block=1``, ``ops._dev_block``), which
    no CPU test runs."""
    n, d = WIDTHS[width]
    br = _tile(d, "float32", min_block_rows(code_bits))
    r = _device_rows(d, br)
    fn = functools.partial(packed_weighted_sum_2d, code_bits=code_bits,
                           n_dev=n, block_rows=br, dev_block=1)
    words = (n * r * code_bits // 32, LANES)
    text = _compile(fn, one_chip, (words, jnp.uint32),
                    ((n, 3), jnp.float32)).as_text()
    assert "tpu_custom_call" in text


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
    _, runner, args = eng.prepare(B.ProposedDigital(params), rounds=20,
                                  trials=2, eval_every=10, seed=0)
    # the kernels take their TPU branch (no interpret mode) only where
    # JAX's default backend is not the CPU, which it is here
    monkeypatch.setattr(ops, "_on_cpu", lambda: False)
    lowered = runner.lower(*[jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                  sharding=one_chip)
                             for a in args])
    assert "f64" not in lowered.as_text()
    text = lowered.compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert calls
    assert not any("f64" in ln for ln in calls)
