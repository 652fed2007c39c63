"""device_idle (device): share of the traced window in which no operation
ran on the device, in per cent."""

from benchlib import trace


def read(run):
    t = run.window.trace
    if t is None or run.platform != "tpu":
        return None
    return 100.0 * (1.0 - trace.busy_s(t) / t.window_s)
