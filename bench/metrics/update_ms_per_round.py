"""Device time of the model update per round: self time of the scan's
ops in the engine's ``fl.update`` scope (projection, w and wall-clock
update, eval-state select), over the window's calls times the cell's
trials x rounds (the denominator of ``scan_ms_per_round``)."""
from bench import spans


def read(ctx):
    return spans.scope_ms_per_round(ctx, "fl.update")
