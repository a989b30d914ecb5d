"""The engine's own spans and scopes, read from a traced window.

``FLEngine.run`` (``src/repro/fl/engine.py``) records host spans with
``jax.profiler.TraceAnnotation``, on the clock of the device trace:

* ``fl.run``, the whole call;
* inside it ``fl.prepare``: the per-trial stream keys and the arguments,
  and in that ``fl.replay``: the NumPy fading/AWGN/selection replay of
  ``rng="replay"``;
* ``fl.dispatch`` (``h2d_bytes``): copying the NumPy arguments to the
  chip, waiting for the copy, and launching the scan;
* ``fl.evaluate`` (``h2d_bytes``): launching the evaluation;
* ``fl.fetch``: the copies of the results to the host.

Its scan step names its parts with ``jax.named_scope``: ``fl.grads``,
``fl.streams``, ``fl.bias``, ``fl.uplink``, ``fl.update``. A device op
event carries only its HLO instruction (``%fusion.50 = ...``), not the
scope, so the scan's ops are mapped to scopes through the compiled scan's
HLO text, where each instruction's ``op_name`` holds the scope.

A trace of a program without these spans or scopes (an older engine) reads
``None`` everywhere here.
"""
from __future__ import annotations

import re
import tempfile

import numpy as np

from bench import trace as tr

#: ``%fused_computation.3 (...) -> ... {`` or ``ENTRY %main.7 (...) -> ... {``
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
#: ``  [ROOT ]%name = <type> <opcode>(<operands>), ...``
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
#: the opcode: the first ``name(`` after the result type
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
#: loop and call machinery: neither given a producer's scope nor a path to one
_CONTROL = {"while", "conditional", "call", "parameter"}
#: an ``fl.*`` segment of an op_name path (``.../fl.grads/jit(f)/dot``)
_SCOPE = re.compile(r"(?:^|[/;(])(fl\.[A-Za-z_]+)(?=[/;)]|$)")


# ------------------------------------------------------------ host spans

def host_spans(path: str) -> list:
    """``[(name, start_ns, end_ns, {stat: value})]`` of the engine's spans
    (names ``fl.*``) on the host's Python thread of an ``.xplane.pb``,
    by start. ``bench/trace.load`` keeps names and times only."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if line.name.startswith("python"):
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats))
                           for e in line.events if e.name.startswith("fl."))
    return sorted(out, key=lambda s: s[1])


def traced(fn) -> list:
    """``host_spans`` of a profiler session, Python tracer off, around
    ``fn()``."""
    import jax

    with tempfile.TemporaryDirectory(prefix="bench_spans_") as log_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        return host_spans(tr.find_xplane(log_dir))


def in_window(ctx, name: str) -> list:
    """The host events called ``name`` inside the window's calls."""
    return [e for e in ctx.trace.host
            if e.name == name and e.start >= ctx.lo and e.end <= ctx.hi]


def idle_ms_per_call(ctx, name: str):
    """Device-idle time inside the window's ``name`` spans, summed and
    divided by the number of calls (ms), as ``host_ms_per_call`` reads
    the calls' own spans; None where the window holds no such span."""
    spans = in_window(ctx, name)
    if not spans:
        return None
    idle = sum(e.dur - tr.union_length(ctx.busy, e.start, e.end)
               for e in spans)
    return idle * 1e-6 / len(ctx.calls)


def call_stats(ctx) -> dict:
    """``{span: {stat: value}}``: the counts the engine's spans carry,
    from one more call of the cell (the warm-up's seed) traced with the
    Python tracer off. The window's trace, as ``bench/trace.load`` keeps
    it, holds no stats; every call of a cell has the same shapes and so
    the same counts. Only ``h2d_mb_per_call`` needs it, until a loader
    keeps the window's own stats."""
    if "fl_call_stats" not in ctx.__dict__:
        from bench import fl

        cell = ctx.cell
        spans = traced(lambda: cell.run(fl.call_seed(cell.base_seed, -1)))
        ctx.__dict__["fl_call_stats"] = {n: s for n, _, _, s in spans}
    return ctx.__dict__["fl_call_stats"]


def h2d_mb_per_call(ctx):
    """Megabytes the window's ``fl.dispatch`` and ``fl.evaluate`` spans
    copy from the host to the device (their ``h2d_bytes``), per call."""
    spans = {n: len(in_window(ctx, n)) for n in ("fl.dispatch",
                                                 "fl.evaluate")}
    if not any(spans.values()):
        return None
    stats = call_stats(ctx)
    total = sum(k * stats[n]["h2d_bytes"] for n, k in spans.items() if k)
    return total * 1e-6 / len(ctx.calls)


# ------------------------------------------------------------ device scopes

def scopes(hlo_text: str, infer: bool = True) -> dict:
    """``{instruction: scope or None}`` for every instruction of a
    compiled HLO module.

    By its own metadata, an instruction's scope is the ``fl.*`` segment of
    its ``op_name``; a fusion takes its root's (through nested fusions),
    else its own. With ``infer``, an instruction that has none takes that
    of the first scoped instruction it fuses, else that of its nearest
    producer in the same computation that has one: the compiler leaves
    some of the ops it rewrites or adds without metadata (a flattened
    scatter, layout copies). A ``while`` and what reaches an op only
    through one (the scan's own bookkeeping) stay unscoped."""
    instrs: dict = {}  # name -> (computation, op_name, calls, operands, opcode)
    roots: dict = {}           # computation -> [root, then the others]
    cur = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and cur is not None:
            is_root, name, rest = m.groups()
            op = _OP_NAME.search(rest)
            calls = _CALLS.search(rest)
            opcode = _OPCODE.search(rest)
            instrs[name] = (cur, op.group(1) if op else "",
                            calls.group(1) if calls else None,
                            _OPERAND.findall(rest.split(", metadata=")[0]),
                            opcode.group(1) if opcode else "")
            roots[cur].insert(0 if is_root else len(roots[cur]), name)
            continue
        m = _COMPUTATION.match(line)
        if m:
            cur = m.group(1)
            roots[cur] = []
    memo: dict = {}

    def own(name):
        """The scope by ``name``'s own metadata, or its fused root's."""
        if (name, 0) not in memo:
            _, op_name, calls, _, _ = instrs[name]
            body = roots.get(calls, ())
            segs = _SCOPE.findall(op_name)
            memo[name, 0] = ((own(body[0]) if body else None)
                             or (segs[-1] if segs else None))
        return memo[name, 0]

    def fused(name):
        """``own``, else the first scoped instruction ``name`` fuses."""
        if (name, 1) not in memo:
            memo[name, 1] = own(name) or next(
                (s for s in map(fused, roots.get(instrs[name][2], ())) if s),
                None)
        return memo[name, 1]

    def scope(name):
        """``fused``, else that of the nearest producer that has one."""
        comp = instrs[name][0]
        if instrs[name][4] in _CONTROL:
            return own(name)
        queue, seen = [name], {name}
        for n in queue:                       # breadth first
            found = fused(n)
            if found:
                return found
            for o in instrs[n][3]:
                if (o not in seen and o in instrs and instrs[o][0] == comp
                        and instrs[o][4] not in _CONTROL):
                    seen.add(o)
                    queue.append(o)
        return None

    return {n: (scope if infer else own)(n) for n in instrs}


def scan_hlo(cell) -> str:
    """The compiled HLO text of the cell's scan for the call's shapes."""
    _, runner, args = cell.engine.prepare(cell.aggregator, seed=0, **cell.kw)
    return runner.lower(*args).compile().as_text()


def scan_ops(trace, lo: float, hi: float) -> list:
    """The op events of the scan program's executions in ``[lo, hi]``."""
    starts = np.array([e.start for e in trace.ops])
    out = []
    for s, e in tr.merge(tr.clip(tr.scan_intervals(trace), lo, hi)):
        i, j = np.searchsorted(starts, [s, e])
        out.extend(ev for ev in trace.ops[i:j] if ev.end <= e)
    return out


def scope_seconds(trace, scope_of: dict, lo: float, hi: float) -> dict:
    """Self time of the scan's ops in ``[lo, hi]`` summed by scope
    (seconds; ``None`` holds the unscoped ops)."""
    out: dict = {}
    for short, secs in tr.self_times(scan_ops(trace, lo, hi), lo,
                                     hi).items():
        key = scope_of.get(short.split(" = ", 1)[0].lstrip("%"))
        out[key] = out.get(key, 0.0) + secs
    return out


def scope_ms_per_round(ctx, scope: str):
    """Self time of the scan's ops in ``scope`` per round (ms): over the
    window's calls, divided by their number times the cell's trials x
    rounds, as ``scan_ms_per_round`` divides. None where the
    window holds no ``fl.run`` span (an engine without scopes) or the
    scope no op."""
    if not in_window(ctx, "fl.run"):
        return None
    if "fl_scope_seconds" not in ctx.__dict__:
        ctx.__dict__["fl_scope_seconds"] = scope_seconds(
            ctx.trace, scopes(scan_hlo(ctx.cell)), ctx.lo, ctx.hi)
    secs = ctx.__dict__["fl_scope_seconds"].get(scope)
    return None if not secs else secs * 1e3 / (
        len(ctx.calls) * ctx.cell.rounds_per_call)
