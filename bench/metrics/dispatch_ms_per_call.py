"""Host time of copying the scan's NumPy arguments to the chip and
launching the scan, per call: the device-idle time inside the engine's
``fl.dispatch`` spans in the window (the span waits for the copy), over
the calls."""
from bench import spans


def read(ctx):
    return spans.idle_ms_per_call(ctx, "fl.dispatch")
