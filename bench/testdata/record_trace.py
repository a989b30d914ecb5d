#!/usr/bin/env python3
"""Record the small chip trace that ``bench/tests`` reduces by hand.

    python3 bench/testdata/record_trace.py <out.xplane.pb>

Two best-channel calls of the engine at N=4 devices, d=2^17 (the fused
quantize -> pack -> packed-sum path), 2 rounds, each inside the harness's
call span, with the Python tracer off so the file stays small.
"""
import glob
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from bench.run import CALL_SPAN  # noqa: E402
from repro.core import baselines as B  # noqa: E402
from repro.core.channel import WirelessConfig, make_deployment  # noqa: E402
from repro.data.loader import FLDataset  # noqa: E402
from repro.fl.engine import FLEngine  # noqa: E402
from repro.fl.tasks import SyntheticHighDimTask  # noqa: E402


def main(out: str) -> None:
    n, d = 4, 1 << 17
    task = SyntheticHighDimTask(d)
    xs, ys = task.device_data(n)
    ds = FLDataset.from_shards([(xs[m], ys[m]) for m in range(n)],
                               xs[0], ys[0])
    dep = make_deployment(WirelessConfig(n_devices=n, seed=1))
    cfg = dep.cfg
    agg = B.BestChannel(dep, d, task.g_max, cfg.energy_per_symbol,
                        cfg.noise_power, cfg.bandwidth_hz, k=n, r_bits=8)
    eng = FLEngine(task, ds, dep, 0.5)
    kw = dict(rounds=2, trials=1, eval_every=2)
    eng.run(agg, seed=0, **kw)
    log_dir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    for i in range(2):
        with jax.profiler.TraceAnnotation(f"{CALL_SPAN}{i}"):
            eng.run(agg, seed=i + 1, **kw)
    jax.profiler.stop_trace()
    src, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    shutil.copy(src, out)
    shutil.rmtree(log_dir)


if __name__ == "__main__":
    main(sys.argv[1])
