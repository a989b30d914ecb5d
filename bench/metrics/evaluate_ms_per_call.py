"""Host time of launching the evaluation (with the copy of its NumPy
data) per call: the device-idle time inside the engine's
``fl.evaluate`` spans in the window, over the calls."""
from bench import spans


def read(ctx):
    return spans.idle_ms_per_call(ctx, "fl.evaluate")
