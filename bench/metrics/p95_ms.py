"""p95_ms: 95th percentile latency of the requests sent in the window,
each counted from its scheduled send time; a missing answer counts as
missing."""

from benchlib.cell import percentile


def read(run):
    return 1000.0 * percentile(run.window.latencies_s(), 95)
