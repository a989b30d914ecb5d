"""Host-to-device bytes per call (MB): the ``h2d_bytes`` of the engine's
``fl.dispatch`` and ``fl.evaluate`` spans in the window, the NumPy
arguments those launches copy, over the calls. A count, not a time."""
from bench import spans


def read(ctx):
    return spans.h2d_mb_per_call(ctx)
