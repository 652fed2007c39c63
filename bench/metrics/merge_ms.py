"""merge_ms (merge): device time per batch of the top-k merge that follows
the scan kernel in the same program, summed from the trace over the
batches begun in the traced window.  The chip's op events carry no scope
name, so the merge is found by place: the batch's ops that begin after its
last scan-kernel op ends (``program.merge_time_by_batch``)."""

from benchlib import program


def read(run):
    t = run.window.trace
    if t is None:
        return None
    per = program.merge_time_by_batch(t, run.kernel)
    if not per:
        return None
    return 1000.0 * sum(per.values()) / len(per)
