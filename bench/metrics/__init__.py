"""Per-layer metrics: one reader per metric, ``read(ctx) -> value | None``.

A reader takes its number from the traced window in :class:`Context`
and returns ``None`` where the trace holds nothing for it (the harness then
leaves the metric out of the line). A share of a roofline or of a peak is
never returned as 0 for lack of data.
"""
from __future__ import annotations

import dataclasses

from bench import trace as tr


@dataclasses.dataclass
class Context:
    """The traced window of one run and what the readers need beside it."""

    trace: tr.Trace
    span_prefix: str       # the harness's per-call TraceAnnotation
    cell: object           # bench.fl.Cell
    config_module: object  # bench/configs/<config>.py
    config: dict
    traffic: dict
    peaks: dict            # this device kind's row of bench/peaks.json
    registry: object

    def __post_init__(self):
        self.calls = tr.spans(self.trace, self.span_prefix)
        if not self.calls:
            raise ValueError("the trace holds no call spans")
        self.lo, self.hi = self.calls[0].start, self.calls[-1].end
        self.busy = tr.busy_intervals(self.trace)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def busy_s(self) -> float:
        return tr.union_length(self.busy, self.lo, self.hi) * 1e-9

    def kernels(self) -> dict:
        """Launches in the window: ``{kernel: [(s, operands, results)]}``.
        A kernel with no count file under ``bench/kernels`` is an error."""
        inside = tr.Trace(modules=[], host=[], ops=[
            e for e in self.trace.ops
            if e.start >= self.lo and e.end <= self.hi])
        out = tr.kernel_events(inside)
        for name in out:
            self.registry.module("kernels", name)
        return out

    def roofline_share(self, kernel: str):
        """Percent of its launches' device time that the chip's roofline
        (the larger of FLOPs over peak and HBM bytes over bandwidth)
        needs; None where the window launched no such kernel."""
        launches = self.kernels().get(kernel)
        if not launches:
            return None
        cost = self.registry.module("kernels", kernel).cost
        need = spent = 0.0
        for seconds, operands, results in launches:
            flops, nbytes = cost(operands, results)
            need += max(flops / self.peaks["bf16_flops_per_s"],
                        nbytes / self.peaks["hbm_bytes_per_s"])
            spent += seconds
        return 100.0 * need / spent

    def breakdown(self, k: int = 10, labelled: int = 200) -> dict:
        """The ``k`` device operations of most self time, and the idle
        time of the ``labelled`` longest gaps summed by what the host was
        doing in them, ``k`` largest first (seconds)."""
        ops = tr.self_times(self.trace.ops, self.lo, self.hi)
        top = sorted(ops.items(), key=lambda kv: -kv[1])[:k]
        longest = sorted(tr.gaps(self.busy, self.lo, self.hi),
                         key=lambda g: g[0] - g[1])[:labelled]
        idle: dict = {}
        for a, b in longest:
            name = tr.label(self.trace, a, b, self.span_prefix)
            idle[name] = idle.get(name, 0.0) + (b - a) * 1e-9
        return {"device_ops": [[n, s] for n, s in top],
                "idle_gaps": sorted(([n, s] for n, s in idle.items()),
                                    key=lambda x: -x[1])[:k]}
