"""Distribution-layer tests.

The multi-device cases run in a subprocess so the main pytest process keeps
the default single CPU device (per the dry-run isolation rule).
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from repro.launch.sharding import ShardingRules, decode_rules
from repro.launch.hlo_cost import analyze_hlo, parse_computations

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

def run_sub(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


class TestShardingRules:
    def setup_method(self):
        # a mesh object is needed only for axis names/sizes; build abstractly
        self.mesh = jax.make_mesh((1, 1), ("data", "model"))

    def test_divisibility_fallback(self):
        import jax as j
        mesh = j.make_mesh((1, 1), ("data", "model"))
        # fake sizes via host mesh won't exercise divisibility; test the
        # rule logic directly with a synthetic mesh-like object
        rules = ShardingRules.default()

        class FakeMesh:
            axis_names = ("data", "model")
            shape = {"data": 16, "model": 16}

        spec = rules.spec_for(FakeMesh(), (32, 4096), ("heads", "embed"))
        assert spec == P("model")
        # 10 heads not divisible by 16 -> replicate
        spec = rules.spec_for(FakeMesh(), (10, 256), ("heads", "head_dim"))
        assert spec == P()

    def test_axis_uniqueness(self):
        rules = ShardingRules.default()

        class FakeMesh:
            axis_names = ("data", "model")
            shape = {"data": 16, "model": 16}

        # (mlp, mlp): second dim must not reuse "model"
        spec = rules.spec_for(FakeMesh(), (2560, 2560), ("lru", "lru"))
        assert spec == P("model")

    def test_decode_rules_batch_one(self):
        class FakeMesh:
            axis_names = ("data", "model")
            shape = {"data": 16, "model": 16}

        r = decode_rules(1, FakeMesh())
        spec = r.spec_for(FakeMesh(), (1, 524288, 8, 128),
                          ("batch", "cache_seq", "kv_heads", "head_dim"))
        # batch=1 unshardable -> cache sequence sharded over data
        assert spec == P(None, "data")
        r2 = decode_rules(128, FakeMesh())
        spec2 = r2.spec_for(FakeMesh(), (128, 32768, 8, 128),
                            ("batch", "cache_seq", "kv_heads", "head_dim"))
        assert spec2 == P("data")        # batch sharded, seq replicated


class TestHLOCost:
    def test_scan_trip_counts(self):
        import jax.numpy as jnp

        def f(x, w):
            def body(c, wi):
                return c @ wi, None
            out, _ = jax.lax.scan(body, x, w)
            return out

        x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
        w = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)
        c = jax.jit(f).lower(x, w).compile()
        cost = analyze_hlo(c.as_text())
        expected = 2 * 128 ** 3 * 8
        assert expected <= cost.flops <= expected * 1.1

    def test_parse_computations_nonempty(self):
        import jax.numpy as jnp
        c = jax.jit(lambda x: x @ x).lower(
            jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
        comps = parse_computations(c.as_text())
        assert comps


@pytest.mark.slow
class TestMultiDevice:
    def test_train_step_aggregators(self):
        out = run_sub("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs import get_config
            from repro.models import make_model, make_batch
            from repro.launch.mesh import auto_mesh
            from repro.launch.steps import make_train_step, fl_round_arrays
            mesh = auto_mesh((4,2), ("data","model"))
            cfg = get_config("qwen3-moe-30b-a3b").scaled_down()
            model = make_model(cfg)
            params = model.init(jax.random.key(0))
            batch = make_batch(cfg, 8, 32, jax.random.key(1))
            for agg in ("ideal", "ota", "digital"):
                sb = make_train_step(model, mesh, aggregator=agg,
                                     batch=8, seq=32)
                fl = fl_round_arrays(mesh, alpha=4.0, noise_scale=1e-4)
                f = jax.jit(sb.fn, in_shardings=sb.in_shardings,
                            out_shardings=sb.out_shardings)
                new_params, loss = f(params, batch, fl, jax.random.key(7))
                assert np.isfinite(float(loss)), agg
                moved = sum(float(jnp.sum(jnp.abs(a.astype(jnp.float32)
                                                  - b.astype(jnp.float32))))
                            for a, b in zip(jax.tree.leaves(params),
                                            jax.tree.leaves(new_params)))
                assert moved > 0, agg
                print("OK", agg, float(loss))
        """)
        assert out.count("OK") == 3

    def test_ota_collective_matches_simulation(self):
        """wireless_psum(ota) == numpy OTA aggregation on the same grads."""
        out = run_sub("""
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import PartitionSpec as P
            from repro.core.collectives import WirelessRound, wireless_psum
            from repro.launch.mesh import auto_mesh
            mesh = auto_mesh((4,), ("data",))
            grads = np.arange(4 * 6, dtype=np.float32).reshape(4, 6)
            weight = np.array([0.5, 0.0, 1.5, 1.0], np.float32)
            alpha = 2.5
            def body(g, w, key):
                r = WirelessRound(weight=w, alpha=jnp.float32(alpha),
                                  noise_scale=jnp.float32(0.0),
                                  levels=jnp.float32(255.0))
                return wireless_psum({"g": g[0]}, r, ("data",), key,
                                     mode="ota", use_kernel=False)["g"]
            f = jax.shard_map(body, mesh=mesh,
                              in_specs=(P("data"), P("data"), P()),
                              out_specs=P(), axis_names={"data"},
                              check_vma=False)
            got = jax.jit(f)(jnp.asarray(grads).reshape(4, 1, 6),
                             jnp.asarray(weight), jax.random.key(0))
            want = (weight[:, None] * grads).sum(0) / alpha
            np.testing.assert_allclose(np.asarray(got).reshape(-1), want,
                                       rtol=1e-6)
            print("OK collective")
        """, devices=4)
        assert "OK collective" in out

    def test_decode_step_multidevice(self):
        out = run_sub("""
            import jax, numpy as np
            from repro.configs import get_config
            from repro.models import make_model
            from repro.launch.mesh import auto_mesh
            from repro.launch.steps import make_decode_step
            mesh = auto_mesh((4,2), ("data","model"))
            for arch in ("gemma3-4b", "falcon-mamba-7b"):
                cfg = get_config(arch).scaled_down()
                model = make_model(cfg)
                sb = make_decode_step(model, mesh, batch=8, cache_len=64)
                sb.lower().compile()
                print("OK", arch)
        """)
        assert out.count("OK") == 2


class TestShardingCoverage:
    def test_all_arch_param_specs_resolve(self):
        """Every assigned arch's full param tree maps to valid specs on the
        production mesh shape (divisibility/uniqueness rules hold)."""
        from repro.configs import REGISTRY
        from repro.models import make_model
        from repro.launch.sharding import ShardingRules

        class FakeMesh:
            axis_names = ("data", "model")
            shape = {"data": 16, "model": 16}

        rules = ShardingRules.default()
        for arch, cfg in REGISTRY.items():
            model = make_model(cfg)
            aparams = model.abstract_params()
            specs = rules.tree_specs(FakeMesh(), aparams, model.axes)
            import jax
            from jax.sharding import PartitionSpec as P
            n_sharded = 0
            for s, leaf in zip(
                    jax.tree.leaves(specs,
                                    is_leaf=lambda x: isinstance(x, P)),
                    jax.tree.leaves(aparams)):
                for i, entry in enumerate(s):
                    if entry is None:
                        continue
                    axes = entry if isinstance(entry, tuple) else (entry,)
                    size = 1
                    for a in axes:
                        size *= FakeMesh.shape[a]
                    assert leaf.shape[i] % size == 0, (arch, s, leaf.shape)
                    n_sharded += 1
            assert n_sharded > 0, f"{arch}: nothing sharded at all"
