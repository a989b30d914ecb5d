"""``chip_smoke.py`` rehearsed on the CPU at a tiny size.

The phases run here with Pallas in interpret mode, so they check paths,
arguments, control flow and the oracle comparisons, not the chip.
``main()`` itself must refuse to run anywhere but on a TPU.
"""
import dataclasses
import importlib.util
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod      # dataclasses resolve it by name
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny(smoke):
    return dataclasses.replace(
        smoke.FULL, quick=True, ota_devices=10, digital_devices=10,
        rounds=6, trials=1, eval_every=3, kappa=3.0, clients_per_round=4,
        payload_devices=4, payload_dim=(1 << 17) + 3, payload_rounds=2,
        design_devices=6)


@pytest.mark.parametrize("phase", ["fig2_ota", "fig2_digital",
                                   "bias_layers", "payload", "design"])
def test_phase_rehearsal(smoke, tiny, phase, capsys):
    fn = dict(smoke.PHASES)[phase]
    res = smoke.run_phase(phase, fn, tiny, on_chip=False)
    assert res["ok"], res["problems"]
    assert capsys.readouterr().out.startswith(f"{phase}: ")


def test_sharded_trials_rehearsal(smoke, tiny):
    """One CPU device: the sharded scan over a one-device mesh against the
    unsharded one."""
    res = smoke.run_phase("sharded_trials", smoke.phase_sharded_trials,
                          tiny, on_chip=False)
    assert res["ok"], res["problems"]


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_main_refuses_without_tpu(smoke, argv, capsys):
    assert smoke.main(argv) != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "Nothing was run" in out.err


def test_compile_cache_honours_env(monkeypatch):
    import jax
    from repro import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    import jax
    from repro import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable() == path      # the same every call
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
