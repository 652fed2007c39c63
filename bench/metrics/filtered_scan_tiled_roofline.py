"""filtered_scan_tiled_roofline (scan): the least time the chip needs for
the scan work the searches need (``benchlib/work.py``, at the peaks of
``benchlib/peaks.py``) over the kernel's device time, in per cent, summed
over the batches begun in the traced window."""

from benchlib import trace


def read(run):
    t = run.window.trace
    if t is None or not run.scan_least_s:
        return None
    per = trace.kernel_time_by_batch(t, run.kernel)
    both = [b for b, s in per.items() if s > 0 and b in run.scan_least_s]
    if not both:
        return None
    least = sum(run.scan_least_s[b] for b in both)
    return 100.0 * least / sum(per[b] for b in both)
