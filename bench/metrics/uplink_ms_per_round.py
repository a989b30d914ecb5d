"""Device time of the uplink per round: self time of the scan's ops in
the engine's ``fl.uplink`` scope (the scheme's ``round_fn``: kernels and
the relayouts around them; bf16 payload rounding), over the window's
calls times the cell's trials x rounds (the denominator of
``scan_ms_per_round``)."""
from bench import spans


def read(ctx):
    return spans.scope_ms_per_round(ctx, "fl.uplink")
