"""send_late_ms (client): 95th percentile of how late the load generator
sent the window's requests against their schedule.  A starved generator
shows here, not as a fast server."""

from benchlib.cell import percentile


def read(run):
    w = run.window
    m = w.in_window
    return 1000.0 * percentile((w.sent - w.target)[m], 95)
