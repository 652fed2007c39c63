"""Reduction of a profiler trace to device busy time, kernel time and gaps.

The benchmark marks its own spans in the trace with
``jax.profiler.TraceAnnotation``: ``bench.window`` around the measured
window, ``bench.batch`` around each call into the search function (stat
``batch``: the batch's index), ``bench.plan`` around the engine's plan
stage.  Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane.  A trace with no such plane (a CPU rehearsal)
takes the host's XLA operation events, those with an ``hlo_op`` stat.
All times here are nanoseconds on the trace's own clock.
"""

from __future__ import annotations

import bisect
import dataclasses
import pathlib
import re
from typing import Dict, List, Optional, Tuple

WINDOW, BATCH, PLAN = "bench.window", "bench.batch", "bench.plan"
_TPU_PLANE = re.compile(r"^/device:TPU:\d+$")


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    stats: dict


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]  # the bench.window span
    spans: List[Span]  # the benchmark's host spans, by start
    device_ops: Dict[int, List[Span]]  # device index -> ops, by start

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


def load(trace_dir) -> "object":
    """The ProfileData of the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(str(files[-1]))


def _span(e) -> Span:
    # a device op's name is its HLO text; keep the instruction's name
    name = e.name.split(" = ", 1)[0].lstrip("%")
    return Span(name, float(e.start_ns), float(e.end_ns), dict(e.stats))


def parse(profile) -> Trace:
    """Collects the benchmark's spans and the device operations."""
    spans, tpu, host_ops = [], {}, []
    for plane in profile.planes:
        if _TPU_PLANE.match(plane.name):
            dev = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    tpu.setdefault(dev, []).extend(_span(e) for e in line.events)
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in (WINDOW, BATCH, PLAN):
                    spans.append(_span(e))
                elif plane.name.startswith("/host:") and any(
                        k == "hlo_op" for k, _ in e.stats):
                    host_ops.append(_span(e))
    windows = [s for s in spans if s.name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(windows)}")
    ops = tpu if tpu else {0: host_ops}
    for v in ops.values():
        v.sort(key=lambda s: s.start)
    spans.sort(key=lambda s: s.start)
    return Trace((windows[0].start, windows[0].end), spans, ops)


def _clip(spans: List[Span], lo: float, hi: float) -> List[Tuple[float, float]]:
    out = []
    for s in spans:
        a, b = max(s.start, lo), min(s.end, hi)
        if b > a:
            out.append((a, b))
    return out


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(tr: Trace) -> float:
    """Seconds in the window in which an operation ran on the device,
    averaged over the devices."""
    lo, hi = tr.window
    per = [sum(b - a for a, b in _union(_clip(ops, lo, hi)))
           for ops in tr.device_ops.values()]
    return sum(per) / max(len(per), 1) / 1e9


def top_ops(tr: Trace, n: int = 10) -> List[List]:
    """The device operations that took most time in the window."""
    lo, hi = tr.window
    tot: Dict[str, float] = {}
    for ops in tr.device_ops.values():
        for s in ops:
            a, b = max(s.start, lo), min(s.end, hi)
            if b > a:
                tot[s.name] = tot.get(s.name, 0.0) + (b - a) / 1e9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: Trace, label) -> List[Tuple[float, float, str]]:
    """Intervals of the window with no operation on device 0, each with
    ``label(start_ns, end_ns)``: what the host was doing."""
    lo, hi = tr.window
    dev = min(tr.device_ops) if tr.device_ops else None
    busy = _union(_clip(tr.device_ops.get(dev, []), lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return [(a, b, label(a, b)) for a, b in gaps]


def host_label(tr: Trace, pending_at) -> "callable":
    """Labels a gap by the benchmark span around its midpoint: ``plan``
    inside the engine's plan stage, ``batch`` elsewhere inside a call into
    the search function, and between calls ``batch assembly`` when requests
    were waiting (``pending_at(t_ns)`` says how many) or ``arrival wait``
    when none were."""
    plans = [s for s in tr.spans if s.name == PLAN]
    batches = [s for s in tr.spans if s.name == BATCH]
    starts = {id(plans): [s.start for s in plans],
              id(batches): [s.start for s in batches]}

    def inside(spans, t):  # spans of one kind do not overlap
        j = bisect.bisect_right(starts[id(spans)], t) - 1
        return j >= 0 and t < spans[j].end

    def label(a, b):
        mid = (a + b) / 2
        if inside(plans, mid):
            return "plan"
        if inside(batches, mid):
            return "batch"
        return "batch assembly" if pending_at(mid) else "arrival wait"

    return label


def kernel_time_by_batch(tr: Trace, pattern: str) -> Dict[int, float]:
    """Seconds of device ops whose name matches ``pattern``, by the batch
    whose ``bench.batch`` span began last before each op began.  Only
    batches that began inside the window count; ops before the first of
    them are left out (they belong to a batch begun before the window)."""
    rx = re.compile(pattern)
    lo, hi = tr.window
    batches = [s for s in tr.spans
               if s.name == BATCH and lo <= s.start < hi]
    starts = [s.start for s in batches]
    out: Dict[int, float] = {int(s.stats["batch"]): 0.0 for s in batches}
    for ops in tr.device_ops.values():
        for s in ops:
            if not rx.search(s.name):
                continue
            j = bisect.bisect_right(starts, s.start) - 1
            if j < 0:
                continue
            b = int(batches[j].stats["batch"])
            out[b] += (s.end - s.start) / 1e9 / len(tr.device_ops)
    return out


def breakdown(tr: Trace, pending_at, n: int = 10) -> Optional[dict]:
    """The result line's ``breakdown``: top device ops and longest gaps."""
    gaps = idle_gaps(tr, host_label(tr, pending_at))
    gaps.sort(key=lambda g: g[0] - g[1])
    return {"device_ops": top_ops(tr, n),
            "idle_gaps": [[lab, (b - a) / 1e9] for a, b, lab in gaps[:n]]}
