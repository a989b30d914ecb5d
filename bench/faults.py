"""Faults planted under the timed path, to show that the check fails them.

``broken(program, how)`` wraps a configuration module's ``program`` so
that the engine it returns is broken underneath the harness:

* ``frozen``: the step leaves the model state as it was (eta = 0);
* ``half_batch``: half of each device's data is left out and the mean
  taken over the rest (payload devices hold only their id: the second half
  of the devices repeats the first);
* ``altered``: one entry of the last state the scan produces is moved by 1.
"""
from __future__ import annotations

FAULTS = ("frozen", "half_batch", "altered")


def broken(program, how: str):
    if how not in FAULTS:
        raise ValueError(f"no fault {how!r}; one of {FAULTS}")

    def program_with_fault(config, traffic, arrays):
        eng, agg, kw = program(config, traffic, arrays)
        if how == "frozen":
            eng.eta = 0.0
        elif how == "half_batch":
            n, rows = eng.xs.shape[:2]
            if rows == 1:
                half = n // 2
                eng.xs[half:] = eng.xs[:n - half]
            else:
                eng.xs = eng.xs[:, :rows // 2]
                eng.ys = eng.ys[:, :rows // 2]
        else:
            get = eng._get_runner

            def get_runner(*a, **k):
                runner = get(*a, **k)

                def altered(*args):
                    ws, walls = runner(*args)
                    return ws.at[0, -1, 0].add(1.0), walls
                altered.lower = runner.lower     # the same scan program
                return altered
            eng._get_runner = get_runner
        return eng, agg, kw
    return program_with_fault
