"""Host time of preparing a call, per call: the device-idle time inside
the engine's ``fl.prepare`` spans in the window (the per-trial stream
keys, the arguments, and the NumPy replay of ``fl.replay`` inside it),
over the calls."""
from bench import spans


def read(ctx):
    return spans.idle_ms_per_call(ctx, "fl.prepare")
