"""scan_ms (scan): device time of the scan kernel per batch, summed from
the trace over the batches begun in the traced window."""

from benchlib import trace


def read(run):
    t = run.window.trace
    if t is None:
        return None
    per = trace.kernel_time_by_batch(t, run.kernel)
    busy = [s for s in per.values() if s > 0]
    if not busy:
        return None
    return 1000.0 * sum(busy) / len(busy)
