"""Device time of the in-scan random streams per round: self time of the
scan's ops in the engine's ``fl.streams`` scope (dither, fast-mode
fading/noise/selection, participation/fault/arrival uniforms), over the
window's calls times the cell's trials x rounds (the denominator of
``scan_ms_per_round``)."""
from bench import spans


def read(ctx):
    return spans.scope_ms_per_round(ctx, "fl.streams")
