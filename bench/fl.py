"""One FL Monte-Carlo cell as the window drives it, and its check.

The window calls the engine's entry for one Monte-Carlo cell,
``FLEngine.run(aggregator, rounds=, trials=, eval_every=, seed=)``, with a
fresh seed per call. The model states at the eval points, which the scan
hands to the engine's evaluation, are kept for a sample of the calls drawn
from the run's seed (reservoir sampling, so the sample is uniform over
however many calls the window holds); after the window the plain
reference recomputes those calls and :func:`readings` compares them.
"""
from __future__ import annotations

import numpy as np


def call_seed(base: int, index: int) -> int:
    """The engine seed of call ``index`` of a run seeded ``base``: a
    31-bit draw from ``SeedSequence((base, index))``; index -1 is the
    warm-up call."""
    ss = np.random.SeedSequence((int(base), int(index) + 1))
    return int(ss.generate_state(1, np.uint32)[0] >> 1)


class Cell:
    """The program's engine and aggregator for one cell's traffic."""

    def __init__(self, engine, aggregator, run_kwargs: dict, base_seed: int,
                 keep: int):
        self.engine, self.aggregator, self.kw = engine, aggregator, run_kwargs
        self.base_seed = base_seed
        self.keep = keep
        self.kept: list = []          # [(index, seed, outputs)]
        self._pick = np.random.default_rng((int(base_seed), 7))
        self._ws = []
        evaluate = engine._evaluate

        def capture(ws):              # the states the scan produced
            self._ws.append(ws)
            return evaluate(ws)
        engine._evaluate = capture

    @property
    def population(self) -> int:
        return self.engine.dep.n_devices

    @property
    def device_rounds_per_call(self) -> int:
        return self.kw["trials"] * self.kw["rounds"] * self.population

    @property
    def rounds_per_call(self) -> int:
        return self.kw["trials"] * self.kw["rounds"]

    def program_bytes(self) -> int:
        """Device bytes the call's compiled scan holds while it runs: its
        arguments, results and temporaries (XLA's memory analysis), which
        the allocator's ``peak_bytes_in_use`` does not count."""
        _, runner, args = self.engine.prepare(self.aggregator, seed=0,
                                              **self.kw)
        m = runner.lower(*args).compile().memory_analysis()
        return int(m.argument_size_in_bytes + m.output_size_in_bytes
                   + m.temp_size_in_bytes - m.alias_size_in_bytes)

    def run(self, seed: int) -> dict:
        log = self.engine.run(self.aggregator, seed=seed, **self.kw)
        return {"ws": self._ws.pop(), "loss": log.global_loss,
                "acc": log.accuracy, "wall": log.wall_time_s}

    def call(self, index: int) -> None:
        """One timed call; its outputs join the sample or are dropped."""
        seed = call_seed(self.base_seed, index)
        out = self.run(seed)
        if len(self.kept) < self.keep:
            self.kept.append((index, seed, out))
        else:
            j = int(self._pick.integers(0, index + 1))
            if j < self.keep:
                self.kept[j] = (index, seed, out)


def readings(got: dict, want: dict) -> dict:
    """The numbers compared between a call and its reference:

    * ``state_gap``: the largest ||w - w_ref|| / ||w_ref|| over trials and
      eval points after the first (both start from the same w0);
    * ``loss_gap``: the largest |F - F_ref| / |F_ref| of the global loss;
    * ``acc_gap``: the largest |acc - acc_ref| of the test accuracy;
    * ``wall_gap``: the largest relative gap of the simulated wall-clock
      at the eval points where it is not zero.
    """
    ws, wr = np.asarray(got["ws"], np.float64), np.asarray(want["ws"],
                                                           np.float64)
    if ws.shape != wr.shape:
        return {"state_gap": np.inf, "loss_gap": np.inf, "acc_gap": np.inf,
                "wall_gap": np.inf}
    num = np.linalg.norm(ws[:, 1:] - wr[:, 1:], axis=-1)
    den = np.maximum(np.linalg.norm(wr[:, 1:], axis=-1), 1e-30)
    lp, lr = np.asarray(got["loss"]), np.asarray(want["loss"])
    wp, wr_ = np.asarray(got["wall"]), np.asarray(want["wall"])
    live = np.abs(wr_) > 0
    return {
        "state_gap": float(np.max(num / den)),
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "acc_gap": float(np.max(np.abs(np.asarray(got["acc"])
                                       - np.asarray(want["acc"])))),
        "wall_gap": float(np.max(np.abs(wp[live] - wr_[live])
                                 / np.abs(wr_[live])) if live.any() else 0.0),
    }
