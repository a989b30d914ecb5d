#!/usr/bin/env python3
"""Record the small chip trace of the engine's spans and scopes that
``tests/bench_harness/test_spans.py`` reduces by hand.

    python3 bench/testdata/record_spans.py <out-stem>

Two calls of a Fig. 2-shaped OTA engine (softmax regression, d=7850,
proposed OTA, AWGN replayed on the host) at N=4 devices of 20 samples,
2 trials x 6 rounds, each inside the harness's call span, with the Python
tracer off so the file stays small. Writes ``<out-stem>.xplane.pb`` and
the compiled scan's HLO text, ``<out-stem>.hlo.txt``, which maps the
trace's op events to the engine's scopes.
"""
import glob
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from bench import fl, spans  # noqa: E402
from bench.registry import Registry  # noqa: E402
from bench.run import CALL_SPAN  # noqa: E402

N = 4
DATA = {"n_train_per_class": 40, "n_test_per_class": 10,
        "samples_per_device": 20}
CALL = {"rounds": 6, "trials": 2, "eval_every": 3}


def main(stem: str) -> None:
    reg = Registry()
    config = reg.config("fig2_mnist_ota")
    config["wireless"]["n_devices"] = N
    config["data"].update(DATA)
    # the stored design's first N devices: a trace fixture, not a design
    config["design"]["gammas"] = config["design"]["gammas"][:N]
    traffic = dict(reg.traffic("full"), **CALL)
    mod = reg.module("configs", "fig2_mnist_ota")
    engine, aggregator, kw = mod.program(config, traffic,
                                         mod.dataset(config))
    cell = fl.Cell(engine, aggregator, kw, base_seed=2 ** 31 + 5, keep=0)
    cell.run(fl.call_seed(cell.base_seed, -1))
    log_dir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    for i in range(2):
        with jax.profiler.TraceAnnotation(f"{CALL_SPAN}{i}"):
            cell.call(i)
    jax.profiler.stop_trace()
    src, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    shutil.copy(src, f"{stem}.xplane.pb")
    shutil.rmtree(log_dir)
    Path(f"{stem}.hlo.txt").write_text(spans.scan_hlo(cell))


if __name__ == "__main__":
    main(sys.argv[1])
