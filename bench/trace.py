"""Reduction of a JAX profiler trace to the benchmark's device numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
``jax.profiler.ProfileData``. The device planes (``/device:TPU:<k>``) give
program executions (line ``XLA Modules``) and operations (``XLA Ops``); the
host plane gives the harness's ``TraceAnnotation`` spans and the Python
frames the profiler records (line ``python3``). All start times are on one
clock, in nanoseconds from the start of the trace.

Definitions used by the per-layer readers:

* busy: the union of the intervals in which a program ran on the device,
  clipped to the window; idle share = 1 - busy / window;
* scan programs: the programs that hold a ``while`` loop (the engine's
  ``lax.scan``); their union over trials x rounds is the scan's time;
* kernels: the ``tpu_custom_call`` operations, named by the Pallas kernel,
  with the operand and result types the HLO text gives them;
* an idle gap: a stretch of the window with no program on the device,
  labelled by whether its middle lies in a call's span and by the
  innermost host frame there.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

#: ``dtype[d0,d1,...]{layout}`` in HLO text; ``S(1)`` in the layout marks
#: an array the compiler placed in the core's VMEM rather than in HBM
_TYPE = re.compile(r"\b(pred|[usf]\d+|bf16|c64|c128)\[([\d,]*)\](\{[^}]*\})?")
#: ``%name.N = <result> custom-call(<operands>), ...tpu_custom_call``
_KERNEL = re.compile(r"^%([A-Za-z_][\w]*?)(?:\.\d+)? = (.*?) custom-call\((.*)$")

_ITEMSIZE = {"pred": 1, "u8": 1, "s8": 1, "u16": 2, "s16": 2, "bf16": 2,
             "f16": 2, "u32": 4, "s32": 4, "f32": 4, "u64": 8, "s64": 8,
             "f64": 8, "c64": 8, "c128": 16}


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float       # ns
    end: float         # ns

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    """The parts of one trace the readers use; one chip's device lines."""

    modules: list      # device program executions (Event)
    ops: list          # device operations (Event)
    host: list         # host frames and spans on the Python thread (Event)
    _host_bounds: tuple = dataclasses.field(default=None, repr=False)

    def host_at(self, t: float) -> list:
        """The host events running at time ``t``."""
        import numpy as np
        if not self.host:
            return []
        if self._host_bounds is None:
            self._host_bounds = (np.array([e.start for e in self.host]),
                                 np.array([e.end for e in self.host]))
        starts, ends = self._host_bounds
        return [self.host[i]
                for i in np.flatnonzero((starts <= t) & (ends >= t))]


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(files)}")
    return files[0]


def load(path: str) -> Trace:
    """Device lines of the first TPU and the host's Python thread."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    modules, ops, host = [], [], []
    devices = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    if not devices:
        raise ValueError(f"{path}: no TPU device plane")
    for plane in pd.planes:
        if plane.name == devices[0].name:
            for line in plane.lines:
                dest = {"XLA Modules": modules, "XLA Ops": ops}.get(line.name)
                if dest is not None:
                    dest.extend(Event(e.name, e.start_ns,
                                      e.start_ns + e.duration_ns)
                                for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name.startswith("python"):
                    host.extend(Event(e.name, e.start_ns,
                                      e.start_ns + e.duration_ns)
                                for e in line.events)
    return Trace(modules=sorted(modules, key=lambda e: e.start),
                 ops=sorted(ops, key=lambda e: e.start),
                 host=sorted(host, key=lambda e: e.start))


# ------------------------------------------------------------ intervals

def merge(intervals) -> list:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def union_length(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in merge(clip(intervals, lo, hi)))


def gaps(intervals, lo: float, hi: float) -> list:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    out, cur = [], lo
    for s, e in merge(clip(intervals, lo, hi)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


# ------------------------------------------------------------ selections

def spans(trace: Trace, prefix: str) -> list:
    """Host spans whose name starts with ``prefix`` (the harness's own)."""
    return [e for e in trace.host if e.name.startswith(prefix)]


def busy_intervals(trace: Trace) -> list:
    return [(e.start, e.end) for e in trace.modules]


def scan_intervals(trace: Trace) -> list:
    """Executions of the programs that hold a ``while`` loop."""
    loops = [e for e in trace.ops if e.name.startswith("%while")]
    names = set()
    j = 0
    for m in trace.modules:
        while j < len(loops) and loops[j].start < m.start:
            j += 1
        if j < len(loops) and loops[j].start < m.end:
            names.add(m.name)
    return [(e.start, e.end) for e in trace.modules if e.name in names]


@dataclasses.dataclass(frozen=True)
class Array:
    """One operand or result of a kernel launch, as the HLO types it."""

    dtype: str
    shape: tuple
    in_vmem: bool = False      # placed in VMEM by the compiler: no HBM bytes

    @property
    def size(self) -> int:
        n = 1
        for x in self.shape:
            n *= x
        return n

    @property
    def nbytes(self) -> int:
        return self.size * _ITEMSIZE[self.dtype]


def parse_types(text: str) -> list:
    """The arrays typed in ``text``, in the order they appear."""
    return [Array(dt, tuple(int(x) for x in dims.split(",") if x),
                  "S(1)" in layout)
            for dt, dims, layout in _TYPE.findall(text)]


def hbm_bytes(arrays) -> int:
    """Bytes of the arrays that live in HBM (read or written once each)."""
    return sum(a.nbytes for a in arrays if not a.in_vmem)


def kernel_events(trace: Trace) -> dict:
    """``{kernel: [(seconds, operand types, result types), ...]}`` for
    every Pallas launch (``tpu_custom_call``) on the device."""
    out: dict = {}
    for e in trace.ops:
        if 'custom_call_target="tpu_custom_call"' not in e.name:
            continue
        m = _KERNEL.match(e.name)
        if m is None:
            raise ValueError(f"unparsed kernel event: {e.name[:200]}")
        name, result, rest = m.groups()
        operands = rest.split("), custom_call_target")[0]
        out.setdefault(name, []).append(
            (e.dur * 1e-9, parse_types(operands), parse_types(result)))
    return out


def self_times(ops, lo: float, hi: float) -> dict:
    """Seconds each device operation ran with none of its nested
    operations running (``while`` bodies nest in the loop's event)."""
    out: dict = {}
    stack: list = []          # [event, child time]

    def close(item):
        ev, child = item
        s, e = max(ev.start, lo), min(ev.end, hi)
        if e > s:
            key = short_name(ev.name)
            out[key] = out.get(key, 0.0) + max(0.0, (e - s) - child) * 1e-9
        if stack:
            stack[-1][1] += max(0.0, min(ev.end, hi) - max(ev.start, lo))

    for ev in ops:
        while stack and stack[-1][0].end <= ev.start:
            close(stack.pop())
        stack.append([ev, 0.0])
    while stack:
        close(stack.pop())
    return out


def short_name(hlo: str) -> str:
    """``%fusion.50 = f32[500000]`` from a full HLO instruction."""
    head = hlo.split(" = ", 1)
    if len(head) < 2:
        return hlo[:100]
    res = _TYPE.search(head[1])
    ty = f"{res.group(1)}[{res.group(2)}]" if res else head[1][:40]
    return f"{head[0]} = {ty}"[:100]


def label(trace: Trace, lo: float, hi: float, span_prefix: str) -> str:
    """What the host did in the middle of ``[lo, hi]``: inside a call's
    span or between calls, and the innermost host frame there."""
    inside = trace.host_at(0.5 * (lo + hi))
    where = ("in call" if any(e.name.startswith(span_prefix) for e in inside)
             else "between calls")
    frames = [e for e in inside if not e.name.startswith(span_prefix)]
    what = min(frames, key=lambda e: e.dur).name if frames else "-"
    return f"{where}: {what}"[:120]
