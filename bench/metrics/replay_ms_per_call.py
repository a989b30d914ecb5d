"""Host time of the NumPy fading/AWGN/selection replay per call: the
device-idle time inside the engine's ``fl.replay`` spans in the window,
over the calls."""
from bench import spans


def read(ctx):
    return spans.idle_ms_per_call(ctx, "fl.replay")
