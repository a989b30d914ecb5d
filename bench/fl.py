"""One FL Monte-Carlo cell as the window drives it, and its check.

The window calls the engine's entry for one Monte-Carlo cell,
``FLEngine.run(aggregator, rounds=, trials=, eval_every=, seed=)``, with a
fresh seed per call. The model states at the eval points, which the scan
hands to the engine's evaluation, are kept for a sample of the calls drawn
from the run's seed (reservoir sampling, so the sample is uniform over
however many calls the window holds); after the window the plain
reference recomputes those calls and :func:`readings` compares them.

Kept states leave the chip: a kept call's states start their copy to the
host when the call returns and are held as a host float32 array from the
next call's return on, so the chip holds the states of the current call
and at most those of the one before it, at any model width.
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
        self._pending = None          # the last kept call's outputs
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
        """One timed call; its outputs join the sample or are dropped.

        Whether they join is drawn before the call, with the same draws in
        the same order as a draw after it, so a run seed samples the same
        calls. A kept call's states start their copy to the host as it
        returns; the kept call before it is settled on the host first."""
        slot = len(self.kept)
        if slot >= self.keep:
            j = int(self._pick.integers(0, index + 1))
            slot = j if j < self.keep else None
        seed = call_seed(self.base_seed, index)
        out = self.run(seed)
        self.settle()
        if slot is None:
            return                    # its states go with ``out``
        if hasattr(out["ws"], "copy_to_host_async"):   # on a device
            out["ws"].copy_to_host_async()
        entry = (index, seed, out)
        if slot < len(self.kept):
            self.kept[slot] = entry
        else:
            self.kept.append(entry)
        self._pending = out

    def settle(self) -> None:
        """Hold the last kept call's states as a host array and let their
        device buffer go (the window's end calls this too)."""
        if self._pending is not None:
            to_host(self._pending)
            self._pending = None


def to_host(out: dict) -> dict:
    """``out["ws"]`` as a host float32 array, in place: the device array
    is dropped, so its buffer is freed once nothing else holds it."""
    ws = out["ws"]
    host = np.asarray(ws, np.float32)
    devices = getattr(ws, "devices", None)
    if devices is not None and {d.platform for d in devices()} == {"cpu"}:
        host = host.copy()            # a CPU array's view holds its buffer
    out["ws"] = host
    return out


#: Elements of each float32 operand converted to float64 at a time.
CHUNK = 1 << 22


def _square_sums(ws: np.ndarray, wr: np.ndarray) -> np.ndarray:
    """Float64 sums of squares per trial and eval point after the first:
    ``[||w - w_ref||^2, ||w_ref||^2, ||w_ref - w_ref[:, 0]||^2]``, over
    chunks of about :data:`CHUNK` elements, with no whole-array float64
    copy."""
    trials, E, d = wr.shape
    out = np.zeros((3, trials, E - 1))
    cols = min(d, CHUNK)
    rows = max(1, CHUNK // cols)
    for t in range(trials):
        for c in range(0, d, cols):
            r0 = wr[t, 0, c:c + cols].astype(np.float64)
            for e in range(1, E, rows):
                got = ws[t, e:e + rows, c:c + cols].astype(np.float64)
                ref = wr[t, e:e + rows, c:c + cols].astype(np.float64)
                at = slice(e - 1, e - 1 + len(ref))
                got -= ref
                out[0, t, at] += np.sum(got * got, axis=-1)
                out[1, t, at] += np.sum(ref * ref, axis=-1)
                ref -= r0
                out[2, t, at] += np.sum(ref * ref, axis=-1)
    return out


def readings(got: dict, want: dict) -> dict:
    """The numbers compared between a call and its reference:

    * ``state_gap``: the largest ||w - w_ref|| / ||w_ref|| over trials and
      eval points after the first (both start from the same w0);
    * ``update_gap``: the largest ||w - w_ref|| / ||w_ref - w_ref[:, 0]||
      over the same points: the gap against the reference's update, which
      w0 does not swamp where it is large;
    * ``loss_gap``: the largest |F - F_ref| / |F_ref| of the global loss;
    * ``acc_gap``: the largest |acc - acc_ref| of the test accuracy;
    * ``wall_gap``: the largest relative gap of the simulated wall-clock
      at the eval points where it is not zero.

    The states' norms are float64 sums over chunks of the float32 arrays.
    """
    ws, wr = np.asarray(got["ws"]), np.asarray(want["ws"])
    if ws.shape != wr.shape:
        return {"state_gap": np.inf, "update_gap": np.inf,
                "loss_gap": np.inf, "acc_gap": np.inf, "wall_gap": np.inf}
    num, ref, upd = np.sqrt(_square_sums(ws, wr))
    lp, lr = np.asarray(got["loss"]), np.asarray(want["loss"])
    wp, wr_ = np.asarray(got["wall"]), np.asarray(want["wall"])
    live = np.abs(wr_) > 0
    return {
        "state_gap": float(np.max(num / np.maximum(ref, 1e-30))),
        "update_gap": float(np.max(num / np.maximum(upd, 1e-30))),
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "acc_gap": float(np.max(np.abs(np.asarray(got["acc"])
                                       - np.asarray(want["acc"])))),
        "wall_gap": float(np.max(np.abs(wp[live] - wr_[live])
                                 / np.abs(wr_[live])) if live.any() else 0.0),
    }
