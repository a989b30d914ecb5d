"""Host time per call: the part of each call's span in which no program
ran on the device (replay precompute, evaluation on the host, dispatch),
summed over the calls in the window and divided by their number."""
from bench import trace as tr


def read(ctx):
    idle = sum((c.end - c.start) - tr.union_length(ctx.busy, c.start, c.end)
               for c in ctx.calls)
    return idle * 1e-6 / len(ctx.calls)
