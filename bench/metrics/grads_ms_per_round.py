"""Device time of the local gradients per round: self time of the scan's
ops in the engine's ``fl.grads`` scope, over the window's calls times
the cell's trials x rounds (the denominator of ``scan_ms_per_round``)."""
from bench import spans


def read(ctx):
    return spans.scope_ms_per_round(ctx, "fl.grads")
