"""Engine-vs-oracle parity: how close the f32 engine must track the oracle.

The JAX engine (``fl/engine.py``) runs the device path in float32; the
NumPy trainer (``fl/trainer.py``) is the float64 reference, and runs its
task programs on the host CPU. Both consume the same random streams, and
the counter-based ones (dither, batch, fault, participation, arrival) give
bit-identical realizations in both (``core.rngstream.f32_table``). What is
left is float32 round-off, and each tolerance below is sized from where
that round-off goes.
"""
from __future__ import annotations

import numpy as np

#: Global loss and squared optimality error, relative, for schemes that
#: send the gradient unquantized (OTA and ideal): f32 model state over T
#: rounds. Fig. 2 OTA at paper width (20 rounds, engine on a TPU v5e,
#: oracle on its host CPU) reads at most 1.1e-5; the same cell with the
#: engine's matmuls left at the TPU default, one bf16 pass, reads 1.4e-4
#: and 1.8e-4, outside this bound.
LOSS_RTOL = 1e-4
#: The same for digital schemes, which quantize the f32 gradient: where a
#: dither lands within f32 rounding of a stochastic-rounding boundary,
#: that coordinate rounds to the other grid point -- a whole step
#: 2m/(2^r - 1), of the order of the gradient itself at 1-3 bits. A run
#: meets a handful of such flips; the largest loss difference they cause
#: on the parity tests is about 2e-3.
QUANTIZED_LOSS_RTOL = 5e-3
#: Absolute floor for the relative loss test (losses near zero).
LOSS_ATOL = 1e-6

#: Test accuracy moves in steps of 1/|test set|: a weight perturbation of
#: f32 size moves the test points that sit on a decision boundary. This
#: many may change side.
ACC_FLIPS = 3

#: Cumulative wall-clock is an f32 sum over T rounds of per-round
#: latencies: relative error up to about T * 2^-24, 1e-5 for T <= 160.
WALL_RTOL = 1e-5
#: Schemes whose latency depends on the model state. Best Channel-Norm
#: gives each selected device round(r_total * its share of the gradient
#: norms) bits. Once a quantizer flip has moved the f32 model, the norms
#: drift from the oracle's (2e-3 relative by round 17 of Fig. 2 digital),
#: and a share near a half-bit rounds the other way: one device sends one
#: bit per entry more or less that round. Read on Fig. 2 digital (N=10,
#: 20 rounds, 2 trials): round 16 of trial 0 gives bits [7, 7, 5, 5]
#: against the oracle's [8, 7, 5, 5], with the same devices selected,
#: and the wall-clock at round 20 moves by 2.4e-3 on the CPU. On a TPU
#: v5e the same cell read 2.4e-3 with the oracle's gradients computed on
#: the chip and 6.2e-6 with the oracle on the host: whether a share
#: crosses a half-bit depends on how the round-off drifts.
STATE_SCHEDULED_WALL_RTOL = {"Best Channel-Norm": 1e-2}


def _close(name, got, want, rtol, atol):
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return [f"{name}: shape {got.shape} != {want.shape}"]
    err = np.abs(got - want)
    bound = atol + rtol * np.abs(want)
    if np.all(err <= bound):
        return []
    worst = int(np.argmax(err - bound))
    return [f"{name}: {int(np.sum(err > bound))}/{err.size} outside "
            f"rtol={rtol:g} atol={atol:g}; worst |diff| "
            f"{err.ravel()[worst]:.3g} at {want.ravel()[worst]:.6g}"]


def parity_violations(log_ref, log, n_test: int) -> list:
    """Where ``log`` (engine) leaves the tolerances around ``log_ref``
    (oracle); empty when it tracks. ``n_test`` is the test-set size that
    the accuracies were counted on."""
    if log_ref.scheme != log.scheme:
        return [f"scheme {log.scheme!r} != {log_ref.scheme!r}"]
    if log_ref.quantized != log.quantized:
        return ["one log quantized its uplink, the other did not"]
    if not np.array_equal(log_ref.rounds, log.rounds):
        return ["eval rounds differ"]
    loss_rtol = QUANTIZED_LOSS_RTOL if log_ref.quantized else LOSS_RTOL
    out = _close("loss", log.global_loss, log_ref.global_loss,
                 loss_rtol, LOSS_ATOL)
    out += _close("accuracy", log.accuracy, log_ref.accuracy,
                  0.0, ACC_FLIPS / n_test + 1e-9)
    out += _close("wall_time_s", log.wall_time_s, log_ref.wall_time_s,
                  STATE_SCHEDULED_WALL_RTOL.get(log_ref.scheme, WALL_RTOL),
                  0.0)
    if log_ref.opt_error is not None:
        out += _close("opt_error", log.opt_error, log_ref.opt_error,
                      loss_rtol, LOSS_ATOL)
    return out


def assert_parity(log_ref, log, n_test: int) -> None:
    bad = parity_violations(log_ref, log, n_test)
    if bad:
        raise AssertionError("engine leaves oracle parity:\n  "
                             + "\n  ".join(bad))
