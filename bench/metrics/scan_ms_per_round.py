"""Device time of the engine's scan per simulated round: the union of the
executions of the programs that hold a ``while`` loop, over the calls in
the window times trials x rounds."""
from bench import trace as tr


def read(ctx):
    scan = tr.union_length(tr.scan_intervals(ctx.trace), ctx.lo, ctx.hi)
    if scan <= 0:
        return None
    return scan * 1e-6 / (len(ctx.calls) * ctx.cell.rounds_per_call)
