"""What the program reports about its own serving path, differenced over
the window: the serving loop's stage sums in ``SearchServer.stats``
(``assemble_s``, ``wait_s``, ...) and the engine's stage histograms in
``metrics_text()`` (``stage="plan.device"``, ...).  Both are the counter
twins of the program's ``repro.*`` spans.  A program without the stage
reports nothing: the reader returns None."""

from __future__ import annotations

import bisect
import re
from typing import Dict, Optional

from benchlib import trace


def server_ms(run, key: str) -> Optional[float]:
    """Milliseconds of the server's stage sum ``key`` per served batch."""
    a, b = (run.window.snap[k]["server"] for k in ("start", "end"))
    batches = b["batches"] - a["batches"]
    if key not in b or batches <= 0:
        return None
    return 1000.0 * (b[key] - a[key]) / batches


def _stage(text: str, stage: str):
    rx = re.compile(r'^repro_stage_latency_seconds_(sum|count)'
                    r'\{stage="' + re.escape(stage) + r'"\} (\S+)$', re.M)
    got = dict(rx.findall(text))
    return float(got.get("sum", 0.0)), float(got.get("count", 0.0))


def stage_ms(run, stage: str) -> Optional[float]:
    """Mean milliseconds of the engine's stage ``stage`` per observation."""
    (s0, n0), (s1, n1) = (_stage(run.window.snap[k]["metrics_text"], stage)
                          for k in ("start", "end"))
    if n1 <= n0:
        return None
    return 1000.0 * (s1 - s0) / (n1 - n0)


def merge_time_by_batch(tr, kernel: str) -> Dict[int, float]:
    """Device seconds of each batch's merge: the ops that begin after the
    batch's last scan-kernel op ends (``kernel``, a name pattern), since
    the merge runs right after the kernel in the same program.  A batch is
    the ``bench.batch`` span that began last before an op began (as in
    ``trace.kernel_time_by_batch``); batches with no kernel op are left
    out."""
    rx = re.compile(kernel)
    lo, hi = tr.window
    batches = [s for s in tr.spans
               if s.name == trace.BATCH and lo <= s.start < hi]
    starts = [s.start for s in batches]
    ops: Dict[int, list] = {}
    for dev_ops in tr.device_ops.values():
        for s in dev_ops:
            j = bisect.bisect_right(starts, s.start) - 1
            if j >= 0:
                ops.setdefault(j, []).append(s)
    out = {}
    for j, lst in ops.items():
        end = max((s.end for s in lst if rx.search(s.name)), default=None)
        if end is None:
            continue
        out[int(batches[j].stats["batch"])] = sum(
            s.end - s.start for s in lst if s.start >= end
        ) / 1e9 / len(tr.device_ops)
    return out
