"""Observability of the serving path: spans, stage histograms, and the
Prometheus text rendering of both.

A :class:`span` marks one stage of the work twice over.  It enters a
``jax.profiler.TraceAnnotation`` named ``repro.<name>``, a host event that
the profiler writes into the same trace as the device's operations and on
the same clock, so a gap on the device's timeline can be laid beside the
host stage that was running at the time.  It also reads the host clock on
entry and exit and hands the seconds to a sink (a stage histogram, a
counter), which works with the profiler off.

Span names in the serving path:

    repro.server.drain      SearchServer: waiting until a batch is formed
    repro.server.assemble   stacking the requests, host-to-device puts
    repro.server.dispatch   the call into the search function
    repro.server.wait       answers to the host (device completion + copy)
    repro.server.deliver    bookkeeping and the per-request responses
    repro.engine.plan       SearchEngine.plan, tiled by its three children:
    repro.engine.plan.prep      host work up to the plan program's dispatch
    repro.engine.plan.device    the dispatch and the first read that waits
    repro.engine.plan.tables    bucket choice, host tables, ordering
    repro.engine.fetch / .delta_fold / .scan / .scan_dispatch /
    repro.engine.merge_dispatch  the engine's other stage timers

The server spans carry the server's batch number as the stat ``batch``.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np


class span:
    """``with span(name, sink, **stats):`` — a profiler annotation
    ``repro.<name>`` (with ``stats`` as its metadata) around the block, and
    the block's host seconds handed to ``sink`` on exit, also when the
    block raises.  ``sink`` may be None where the caller reads ``seconds``
    after the block instead.  With the profiler off the annotation costs
    about a microsecond."""

    __slots__ = ("_sink", "_ann", "_t0", "seconds")

    def __init__(self, name: str, sink: Optional[Callable[[float], Any]],
                 **stats):
        self._sink = sink
        self._ann = jax.profiler.TraceAnnotation(f"repro.{name}", **stats)

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.seconds = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        if self._sink is not None:
            self._sink(self.seconds)
        return False


def _flatten_metrics(out: Dict[str, Any], prefix: str, obj: Any) -> None:
    """Recursively flattens nested stats into ``prefix.key`` scalar entries
    (dict values recurse; numbers/bools/strings pass through; anything else
    is stringified so the scrape never chokes on a stray object)."""
    if isinstance(obj, dict):
        for key, val in obj.items():
            _flatten_metrics(out, f"{prefix}.{key}", val)
    elif isinstance(obj, (bool, int, float, str)) or obj is None:
        out[prefix] = obj
    elif isinstance(obj, (np.integer, np.floating)):
        out[prefix] = obj.item()
    else:
        out[prefix] = str(obj)


# Metric leaf names that are monotonically increasing counts — rendered as
# Prometheus counters; every other numeric metric is a gauge.
_PROM_COUNTERS = frozenset((
    "batches", "pipelined_batches", "tiles_scanned", "scan_compilations",
    "blocks_fetched", "blocks_reused", "degraded_batches", "delta_folds",
    "delta_skips", "hits", "misses", "puts", "evictions", "invalidations",
    "prefetched", "errors", "stalled_waits", "failovers",
    "redirected_blocks", "fallback_blocks", "stale_answers", "retries",
    "deadline_misses", "device_hits", "tile_hits", "tile_puts", "l1_hits",
    "l1_misses", "l1_invalidations", "remote_blocks", "blocks_served",
    "adds", "tombstoned", "commits", "scan_compile_count",
    "probes_terminated", "term_segments_skipped",
    "partition_hits", "partition_fallbacks", "partition_rows_scanned",
    "flat_rows_scanned", "delta_interval_skips", "fetches_skipped",
    "fold_passes", "fold_k_passes",
))


def _prom_name(key: str) -> str:
    out = "".join(c if c.isalnum() or c == "_" else "_" for c in key)
    return out if not out[:1].isdigit() else f"_{out}"


def render_prometheus(metrics: Dict[str, Any],
                      prefix: str = "repro") -> str:
    """Flat dotted-key metrics → Prometheus text exposition format.

    Dots become underscores (``engine.blocks_fetched`` →
    ``repro_engine_blocks_fetched``); booleans render as 0/1 gauges;
    strings become an info-style labeled sample
    (``repro_engine_backend{value="xla"} 1``); None is skipped.  Leaf
    names in :data:`_PROM_COUNTERS` are typed ``counter``, the rest
    ``gauge``.
    """
    lines: List[str] = []
    for key in sorted(metrics):
        val = metrics[key]
        if val is None:
            continue
        name = _prom_name(f"{prefix}.{key}")
        leaf = key.rsplit(".", 1)[-1]
        kind = "counter" if leaf in _PROM_COUNTERS else "gauge"
        if isinstance(val, bool):
            lines.append(f"# TYPE {name} gauge")
            lines.append(f"{name} {int(val)}")
        elif isinstance(val, (int, float)):
            lines.append(f"# TYPE {name} {kind}")
            lines.append(f"{name} {val}")
        else:
            label = str(val).replace("\\", "\\\\").replace('"', '\\"')
            lines.append(f"# TYPE {name} gauge")
            lines.append(f'{name}{{value="{label}"}} 1')
    return "\n".join(lines) + "\n"


# Fixed latency bucket upper bounds (seconds) for the per-stage histograms.
# Chosen to straddle the measured stage costs from sub-ms RAM-resident plans
# up to multi-second cold disk fetches; fixed so scrapes from different
# processes aggregate.
_LAT_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                0.25, 0.5, 1.0, 2.5)


class StageHistogram:
    """Fixed-bucket latency histogram, Prometheus-renderable.

    Buckets are cumulative at render time (classic ``le`` semantics, with
    the implicit ``+Inf`` bucket equal to the total count); observation is
    O(#buckets) with no allocation, cheap enough for per-tile scan timing.
    """

    __slots__ = ("counts", "total", "sum")

    def __init__(self):
        self.counts = [0] * len(_LAT_BUCKETS)
        self.total = 0
        self.sum = 0.0

    def observe(self, seconds: float):
        self.total += 1
        self.sum += seconds
        for i, edge in enumerate(_LAT_BUCKETS):
            if seconds <= edge:
                self.counts[i] += 1
                break

    def render(self, name: str, labels: str) -> List[str]:
        lines = []
        cum = 0
        for edge, n in zip(_LAT_BUCKETS, self.counts):
            cum += n
            lines.append(f'{name}_bucket{{{labels},le="{edge}"}} {cum}')
        lines.append(f'{name}_bucket{{{labels},le="+Inf"}} {self.total}')
        lines.append(f"{name}_sum{{{labels}}} {self.sum}")
        lines.append(f"{name}_count{{{labels}}} {self.total}")
        return lines


def render_stage_histograms(hists: Dict[str, StageHistogram],
                            prefix: str = "repro") -> str:
    """``{stage: histogram}`` → Prometheus exposition text (one metric
    family, ``stage`` label per pipeline stage)."""
    if not hists:
        return ""
    name = f"{prefix}_stage_latency_seconds"
    lines = [f"# TYPE {name} histogram"]
    for stage in sorted(hists):
        lines.extend(hists[stage].render(name, f'stage="{stage}"'))
    return "\n".join(lines) + "\n"
