"""Host time of copying a call's results to the host, per call: the
device-idle time inside the engine's ``fl.fetch`` spans in the window,
over the calls (the wait for the scan and the evaluation is busy time,
not counted)."""
from bench import spans


def read(ctx):
    return spans.idle_ms_per_call(ctx, "fl.fetch")
