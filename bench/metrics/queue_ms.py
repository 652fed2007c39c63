"""queue_ms (server): median time from a request's scheduled send to the
start of the batch that served it, over the window's answered requests."""

from benchlib.cell import percentile


def read(run):
    w = run.window
    b = w.batch_of()
    m = w.in_window & (b >= 0)
    if not m.any():
        return None
    return 1000.0 * percentile(w.batch_starts[b[m]] - w.target[m], 50)
