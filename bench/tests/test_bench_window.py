"""What a window records about the host: batch times, waits between
batches, late sends and the collector's pauses."""

import gc
import pathlib
import sys

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchlib import cell as C  # noqa: E402


def window():
    # Window [10, 20).  Batch 0 starts at 10.0 and answers at 10.5; batch
    # 1 starts at 10.9 although request 2 waited since 10.4, answers at
    # 11.0; batch 2 starts at 12.0 with nothing queued before, answers at
    # 12.2.  Request 3 was sent 30 ms late.
    target = np.array([9.95, 10.1, 10.4, 11.9, 11.95])
    sent = target + np.array([0.001, 0.002, 0.001, 0.030, 0.001])
    done = np.array([10.5, 10.5, 11.0, 12.2, 12.2])
    n = len(target)
    return C.Window(
        seconds=10.0, t0=10.0, target=target, sent=sent, done=done,
        failed=np.zeros(n, bool), cls=np.zeros(n, int), class_names=["a"],
        scores=np.zeros((n, 1)), ids=np.zeros((n, 1), int),
        batch_starts=np.array([10.0, 10.9, 12.0]), snap={}, compiles={},
        gc={"pauses": 0}, requests=None)


def test_host_summary_of_a_known_window():
    h = window().host()
    assert h["batch_ms_median"] == 200.0
    assert h["batch_ms_max"] == 500.0
    # only the wait after batch 0 had a request queued: 10.9 - 10.5
    assert np.isclose(h["queued_gap_ms_max"], 400.0)
    assert np.isclose(h["send_late_ms_max"], 30.0)
    assert h["gc"] == {"pauses": 0}


def test_watch_records_collector_pauses():
    w = C.Watch()
    try:
        gc.collect()
    finally:
        w.close()
    got = w.gc_between(0.0, float("inf"))
    assert got["pauses"] >= 1 and got["gen2"] >= 1
    assert got["max_ms"] >= 0.0 and got["total_ms"] >= got["max_ms"]
    assert w._on_gc not in gc.callbacks
