"""The measured window: a closed loop of calls and its arithmetic.

One client plays a sweep worker: it starts the next call when the last one
returns, until a call would start after the deadline. The window runs from
the first call's start to the return of the last call started before the
deadline, so a rate counts all the work and all the time of the window.
"""
from __future__ import annotations

import dataclasses
import statistics
import time
from typing import Callable


@dataclasses.dataclass(frozen=True)
class Call:
    index: int
    start: float       # s, host clock
    end: float

    @property
    def seconds(self) -> float:
        return self.end - self.start


def closed_loop(call: Callable[[int], None], seconds: float,
                clock: Callable[[], float] = time.perf_counter) -> list:
    """Run ``call(i)`` for i = 0, 1, ... until ``seconds`` have passed
    since the first call started; every call that starts runs to its end."""
    calls = []
    t0 = clock()
    deadline = t0 + seconds
    start = t0
    while start < deadline:
        call(len(calls))
        end = clock()
        calls.append(Call(len(calls), start, end))
        start = end
    return calls


def window_seconds(calls) -> float:
    return calls[-1].end - calls[0].start


def rate(calls, work_per_call: float) -> float:
    """Work completed per second over the whole window."""
    return len(calls) * work_per_call / window_seconds(calls)


def percentile(values, q: int) -> float:
    """The q-th percentile (``statistics.quantiles``, inclusive method:
    interpolated between order statistics, never beyond the largest)."""
    values = list(values)
    if len(values) == 1:
        return float(values[0])
    return float(statistics.quantiles(values, n=100,
                                      method="inclusive")[q - 1])
