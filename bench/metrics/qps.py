"""qps: answers completed inside the window, per second of the window.
A request that failed is not counted."""

import numpy as np


def read(run):
    w = run.window
    ok = ~np.isnan(w.done) & ~w.failed
    inside = ok & (w.done >= w.t0) & (w.done < w.t0 + w.seconds)
    return float(inside.sum() / w.seconds)
