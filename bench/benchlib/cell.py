"""One run of a cell: set-up, the open-loop window, and the check.

Set-up has the configuration's deployment (``bench/deployments/<name>.py``)
make its data from the seed and build the program's search function on
it, wraps that in a ``SearchServer`` and warms every batch shape the
traffic reaches.  The window sends the cell's traffic open loop at the
cell's fixed rate; each latency counts from the request's scheduled send
time.  After the window, the program's state is freed and the served
answers are compared with the deployment's plain reference.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import math
import queue
import shutil
import sys
import tempfile
import threading
import time
import types
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import reference, trace as trace_lib, traffic

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
KERNEL = r"filtered_scan|tiled_kernel"  # the scan kernel's trace names
WARM_SIZES = (1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64)
# the window's trace: device ops and the benchmark's annotations, without
# the Python tracer, whose cost on every call would slow the host path
TRACE_OPTIONS = jax.profiler.ProfileOptions()
TRACE_OPTIONS.python_tracer_level = 0


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)


class Watch:
    """Backend compiles and persistent-cache hits, and pauses of Python's
    garbage collector, each with its time.

    JAX reports a backend compile event for every program it compiles or
    loads from the persistent cache; a cache hit also reports a hit.  A
    collector pause holds every thread of the process, the server's and
    the load generator's."""

    def __init__(self):
        self.compiles: List[tuple] = []  # (monotonic, seconds)
        self.hits: List[float] = []
        self.pauses: List[tuple] = []  # (monotonic start, seconds, gen)
        self._gc_t0 = None
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)
        gc.callbacks.append(self._on_gc)

    def _on_dur(self, name, secs, **_):
        if name == BACKEND_COMPILE:
            self.compiles.append((time.monotonic(), secs))

    def _on_event(self, name, **_):
        if name == CACHE_HIT:
            self.hits.append(time.monotonic())

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.monotonic()
        elif self._gc_t0 is not None:
            self.pauses.append((self._gc_t0, time.monotonic() - self._gc_t0,
                                info["generation"]))
            self._gc_t0 = None

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._on_dur)
        jax.monitoring.unregister_event_listener(self._on_event)
        gc.callbacks.remove(self._on_gc)

    def between(self, a: float, b: float) -> dict:
        c = [s for t, s in self.compiles if a <= t < b]
        h = sum(1 for t in self.hits if a <= t < b)
        return dict(programs=len(c), cache_hits=h, compiled=len(c) - h,
                    seconds=round(sum(c), 3))

    def gc_between(self, a: float, b: float) -> dict:
        p = [(d, g) for t, d, g in self.pauses if a <= t < b]
        return dict(pauses=len(p), gen2=sum(1 for _, g in p if g == 2),
                    total_ms=round(1e3 * sum(d for d, _ in p), 3),
                    max_ms=round(1e3 * max((d for d, _ in p), default=0), 3))


class Recorder:
    """The search function as the server sees it, plus the benchmark's
    host spans: ``bench.batch`` around each call, ``bench.plan`` around the
    engine's plan stage.  Records when each batch began."""

    def __init__(self, fn, fault: Optional[Callable] = None):
        self.fn, self.fault = fn, fault
        self.starts: List[float] = []
        self.degraded = fn.degraded
        engine = fn.engine
        plan = engine.plan

        def traced_plan(queries, fspec):
            with jax.profiler.TraceAnnotation(trace_lib.PLAN):
                return plan(queries, fspec)

        engine.plan = traced_plan  # this engine instance only

    def __call__(self, queries, fspec, shard_ok=None):
        i = len(self.starts)
        self.starts.append(time.monotonic())
        with jax.profiler.TraceAnnotation(trace_lib.BATCH, batch=i):
            out = self.fn(queries, fspec, shard_ok)
        return out if self.fault is None else self.fault(queries, fspec, out)


@dataclasses.dataclass
class System:
    """The program under test, built on one run's data."""

    cfg: dict
    dep: object  # the deployment: the run's data, requests and reference
    fn: object = None
    server: object = None
    recorder: Optional[Recorder] = None
    sizes: dict = dataclasses.field(default_factory=dict)
    u_caps: dict = dataclasses.field(default_factory=dict)  # warmed
    times: dict = dataclasses.field(default_factory=dict)  # set-up phases

    def free(self):
        """Stops the server and drops the program's state."""
        if self.server is not None:
            self.server.stop()
        if self.fn is not None:
            self.fn.close()
        self.dep.free()
        self.fn = self.server = self.recorder = None
        gc.collect()


def peak_bytes() -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks, default=0))


def build(cfg: dict, deployment, seed: int, *, on_tpu: bool,
          variant: str = "", fault: Optional[Callable] = None) -> System:
    """The deployment's data from the seed, then the program's search
    function on it (``variant`` picks another build of the program, such
    as a control) and the server around it."""
    from repro.core.serving import SearchServer

    t = time.perf_counter()
    dep = deployment.Deployment(cfg, seed)
    fn, server_args, sizes = dep.build(variant)
    if on_tpu and fn.engine.backend != "pallas":
        raise RuntimeError(f"engine chose backend {fn.engine.backend!r} on "
                           f"a TPU, not the compiled kernel 'pallas'")
    rec = Recorder(fn, fault)
    server = SearchServer(rec, batch_size=cfg["batch"], **server_args)
    sizes["build_s"] = round(time.perf_counter() - t, 3)
    return System(cfg, dep, fn, server, rec, sizes)


def direct(system: System, r, rows):
    """The requests ``rows`` of ``r`` as one batch through the search
    function, padded as the server pads."""
    queries, fspec = system.dep.batch(r, rows, system.cfg["batch"])
    s, i = system.fn(queries, fspec, jnp.ones((1,), bool))
    return np.asarray(s)[:len(rows)], np.asarray(i)[:len(rows)]


def warm(system: System, mix: dict, seed: int) -> dict:
    """Every batch shape the traffic reaches: partial batches of the mix's
    own requests, from one query to a full batch, on two draws."""
    size = system.cfg["batch"]
    rng = np.random.default_rng([seed, 3])
    for _ in range(2):
        r = system.dep.draw(mix, size, rng, stream=3)
        for b in WARM_SIZES:
            if b <= size:
                direct(system, r, np.arange(b))
    return dict(system.fn.engine.stats.u_cap_hist)


def start(cfg: dict, deployment, mix: dict, seed: int, *, on_tpu: bool,
          variant: str = "", fault: Optional[Callable] = None) -> System:
    """Set-up: the data and the program (``build``), every shape the
    traffic reaches warmed (``warm``), and the server started."""
    t = time.monotonic()
    system = build(cfg, deployment, seed, on_tpu=on_tpu, variant=variant,
                   fault=fault)
    t_built = time.monotonic()
    system.u_caps = warm(system, mix, seed)
    system.times = dict(build_s=t_built - t,
                        warm_s=time.monotonic() - t_built)
    log("sizes: " + json.dumps(system.sizes))
    system.server.start()
    return system


@dataclasses.dataclass
class Window:
    """What one open-loop window recorded (monotonic seconds)."""

    seconds: float
    t0: float
    target: np.ndarray  # [n] scheduled send times
    sent: np.ndarray  # [n] actual send times
    done: np.ndarray  # [n] answer times, nan where none came
    failed: np.ndarray  # [n] the batch raised
    cls: np.ndarray  # [n] filter class
    class_names: list
    scores: np.ndarray  # [n, k]
    ids: np.ndarray  # [n, k]
    batch_starts: np.ndarray  # every batch the server ran, by start
    snap: Dict[str, dict]  # "start"/"end" counters at the window's edges
    compiles: dict  # compiles inside the window
    gc: dict  # the garbage collector's pauses inside the window
    requests: traffic.Requests  # the deployment's, with its payload
    trace: Optional[trace_lib.Trace] = None

    @property
    def in_window(self) -> np.ndarray:
        return (self.target >= self.t0) & (self.target < self.t0 + self.seconds)

    def latencies_s(self) -> np.ndarray:
        """Latency of each request sent in the window, from its scheduled
        send time to its answer.  A request with no answer, or a failed
        one, counts as missing: it is given the time from its send to the
        end of the wait for answers, a minute past the close."""
        late = np.isnan(self.done) | self.failed
        done = np.where(late, self.t0 + self.seconds + 60.0, self.done)
        return (done - self.target)[self.in_window]

    def batch_of(self) -> np.ndarray:
        """[n] index of the batch that served each request, -1 if none."""
        j = np.searchsorted(self.batch_starts, self.done, side="right") - 1
        return np.where(np.isnan(self.done), -1, j)

    def host(self) -> dict:
        """Where the window's time went on the host: the longest batch
        (start to answers), the longest wait between one batch's answers
        and the next batch's start while requests were queued, how late
        the generator sent at worst, and the collector's pauses."""
        b = self.batch_of()
        ok = b >= 0
        served = np.unique(b[ok])
        end = np.full(len(self.batch_starts), np.nan)
        np.fmax.at(end, b[ok], self.done[ok])
        first = np.full(len(self.batch_starts), np.inf)
        np.minimum.at(first, b[ok], self.sent[ok])
        inside = served[(self.batch_starts[served] >= self.t0)
                        & (self.batch_starts[served] < self.t0 + self.seconds)]
        dur = end[inside] - self.batch_starts[inside]
        nxt = inside[inside + 1 < len(self.batch_starts)]
        queued = first[nxt + 1] < end[nxt]
        gap = (self.batch_starts[nxt + 1] - end[nxt])[queued]
        late = (self.sent - self.target)[self.in_window]
        return dict(
            batch_ms_median=round(1e3 * float(np.median(dur)), 3)
            if len(dur) else None,
            batch_ms_max=round(1e3 * float(dur.max()), 3) if len(dur) else None,
            queued_gap_ms_max=round(1e3 * float(np.nanmax(gap, initial=0)), 3),
            send_late_ms_max=round(1e3 * float(late.max(initial=0)), 3),
            gc=self.gc)

    def pending(self) -> Callable[[float], int]:
        """``f(t)``: requests sent by ``t`` whose batch had not begun."""
        b = self.batch_of()
        start = np.where(b >= 0, self.batch_starts[np.maximum(b, 0)], np.inf)
        sent, begun = np.sort(self.sent), np.sort(start)
        return lambda t: int(np.searchsorted(sent, t, side="right")
                             - np.searchsorted(begun, t, side="right"))


def sleep_until(t: float):
    d = t - time.monotonic()
    if d > 0:
        time.sleep(d)


def snapshot(system: System) -> dict:
    eng = system.fn.engine
    return dict(server=dict(system.server.stats),
                u_cap_hist=dict(eng.stats.u_cap_hist),
                metrics_text=eng.metrics_text())


def open_loop(system: System, mix: dict, rate: float, seconds: float,
              seed: int, watch: Watch, trace: bool = False) -> Window:
    """Sends the mix at ``rate`` for ``seconds`` after its lead-in, waits
    for every answer (a minute past the close at most) and returns the
    record.  With ``trace`` the profiler traces the window, into a
    directory of this run's own under ``TMPDIR`` that is removed after."""
    rel = traffic.schedule(mix, rate, seconds, np.random.default_rng([seed, 1]))
    r = system.dep.draw(mix, len(rel), np.random.default_rng([seed, 2]),
                        stream=2)
    n, k = len(rel), system.cfg["top_k"]
    submit_args = system.dep.submit_args
    sent = np.full(n, np.nan)
    futs: list = [None] * n
    server = system.server
    t0 = time.monotonic() + float(mix["lead_in_s"]) + 0.1
    target = t0 + rel

    def send():
        for i in range(n):
            sleep_until(target[i])
            sent[i] = time.monotonic()
            futs[i] = server.submit(*submit_args(r, i))

    sender = threading.Thread(target=send, daemon=True)
    snap = {}
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        sender.start()
        try:
            if trace_dir is not None:
                sleep_until(t0 - 1.0)
                jax.profiler.start_trace(trace_dir,
                                         profiler_options=TRACE_OPTIONS)
            sleep_until(t0)
            with jax.profiler.TraceAnnotation(trace_lib.WINDOW):
                snap["start"] = snapshot(system)
                sleep_until(t0 + seconds)
                snap["end"] = snapshot(system)
        finally:
            if trace_dir is not None:
                jax.profiler.stop_trace()
            sender.join()
        deadline = t0 + seconds + 60.0
        done = np.full(n, np.nan)
        failed = np.zeros(n, bool)
        scores = np.full((n, k), reference.NEG, np.float32)
        ids = np.full((n, k), -1, np.int32)
        for i in range(n):
            try:
                resp = futs[i].get(
                    timeout=max(deadline - time.monotonic(), 1e-3))
            except queue.Empty:
                continue
            except Exception:  # the batch raised; the request failed
                failed[i] = True
                continue
            done[i] = sent[i] + resp.latency_s
            scores[i], ids[i] = resp.scores, resp.ids
        tr = (trace_lib.parse(trace_lib.load(trace_dir))
              if trace_dir is not None else None)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    return Window(seconds, t0, target, sent, done, failed, r.cls,
                  r.class_names, scores, ids,
                  np.asarray(system.recorder.starts), snap,
                  watch.between(t0, t0 + seconds),
                  watch.gc_between(t0, t0 + seconds), r, tr)


def scan_work(system: System, win: Window, device_kind: str) -> dict:
    """Least scan time of each batch begun in the traced window, from the
    work the search needs (the deployment's ``work``) and the chip's
    peaks."""
    from benchlib.peaks import least_seconds

    if win.trace is None:
        return {}
    lo, hi = win.trace.window
    traced = [int(s.stats["batch"]) for s in win.trace.spans
              if s.name == trace_lib.BATCH and lo <= s.start < hi]
    if not traced:
        return {}
    b_of = win.batch_of()
    size = system.cfg["batch"]
    out = {}
    for b in traced:
        rows = np.nonzero(b_of == b)[0]
        if len(rows) == 0:
            continue
        # answers are mapped to batches by time, so under a full queue a
        # batch can take a few of its neighbour's: those count as one more
        # batch of work, never as a batch larger than the server's
        work = [system.dep.work(win.requests, rows[i:i + size], size)
                for i in range(0, len(rows), size)]
        out[b] = least_seconds(device_kind, sum(w[0] for w in work),
                               sum(w[1] for w in work))
    return out


def view(win: Window, *, batch_size: int, device_kind: str,
         platform: str, setup_s: Optional[float] = None,
         scan_least_s: Optional[dict] = None):
    """Everything a metric reader (``bench/metrics/<name>.py``) may read
    about a window."""
    return types.SimpleNamespace(
        seconds=win.seconds, setup_s=setup_s, window=win,
        batch_size=batch_size, device_kind=device_kind, platform=platform,
        scan_least_s=scan_least_s or {}, kernel=KERNEL)


def serve(cfg: dict, deployment, mix: dict, params: dict, seed: int,
          seconds: float, device: dict, *, trace: bool = False,
          variant: str = "", fault: Optional[Callable] = None,
          t_start: Optional[float] = None):
    """One run of a cell: set-up, one open-loop window at the cell's rate,
    the program's state freed, then the check.  Records the device's peak
    memory in ``device``.  Returns the window's ``view`` and the verdict
    of ``check``."""
    on_tpu = device["platform"] == "tpu"
    watch = Watch()
    t_start = time.monotonic() if t_start is None else t_start
    try:
        t_init = time.monotonic()
        system = start(cfg, deployment, mix, seed, on_tpu=on_tpu,
                       variant=variant, fault=fault)
        t_started = time.monotonic()
        win = open_loop(system, mix, params["rate_qps"], seconds, seed,
                        watch, trace)
        log("setup: " + json.dumps(dict(
            start_s=t_init - t_start, **system.times,
            lead_in_s=win.t0 - t_started)))
        log("compile: " + json.dumps(dict(
            setup=watch.between(t_start, win.t0), window=win.compiles,
            u_cap_buckets_warmed=sorted(system.u_caps))))
        log("host: " + json.dumps(win.host()))
        device["memory_peak_bytes"] = peak_bytes()
        work = (scan_work(system, win, device["kind"])
                if trace and on_tpu else {})
        system.free()
        verdict = check(system, win, params["limits"],
                        params["check_requests"], seed)
    finally:
        watch.close()
    log("check: " + json.dumps(verdict["info"]))
    return view(win, batch_size=cfg["batch"], device_kind=device["kind"],
                platform=device["platform"], setup_s=win.t0 - t_start,
                scan_least_s=work), verdict


def check(system: System, win: Window, limits: dict, n_check: int,
          seed: int) -> dict:
    """The comparison that decides ``correct``.

    Every request sent in the window must be answered; every answer must
    name distinct rows in range that pass its filter; a sample drawn from
    the seed is compared with the reference, score by score and id by id.
    """
    cfg, dep = system.cfg, system.dep
    r = win.requests
    w = np.nonzero(win.in_window)[0]
    answered = w[~np.isnan(win.done[w])]
    missing = len(w) - len(answered) + int(win.failed[w].sum())
    faults = dep.answer_faults(win.ids[answered], r, answered)
    rng = np.random.default_rng([seed, 4])
    pick = np.sort(rng.choice(answered, size=min(n_check, len(answered)),
                              replace=False))
    if len(pick) == 0:
        raise RuntimeError("no request sent in the window was answered")
    t = cfg["n_probes"]
    probes, pv = dep.probe_lists(r.queries[pick], t)
    ref_s, ref_i, bf_i = dep.search(r, pick, probes[:, :t], cfg["top_k"])
    got_s, got_i = win.scores[pick], win.ids[pick]
    tol = limits["score_gap"]
    cmp = reference.compare(got_s, got_i, ref_s, ref_i, tol)
    gap = np.abs(got_s.astype(np.float64) - ref_s) / (1 + np.abs(ref_s))
    gap = np.where((ref_s > reference.NEG / 2) | (got_s > reference.NEG / 2),
                   gap, 0.0).max(axis=1)
    # a query whose last probed list and the next one score within the
    # tolerance may be served from either: compare the rest with the other
    near = np.abs(pv[:, t - 1] - pv[:, t]) <= tol * (1 + np.abs(pv[:, t]))
    redo = [j for j in cmp["bad"] if near[j]]
    bad = set(cmp["bad"])
    if redo:
        alt = probes[redo][:, list(range(t - 1)) + [t]]
        a_s, a_i, _ = dep.search(r, pick[redo], alt, cfg["top_k"])
        c2 = reference.compare(got_s[redo], got_i[redo], a_s, a_i, tol)
        for j, row in enumerate(redo):
            if j not in c2["bad"]:
                bad.discard(row)
                g = np.abs(got_s[row].astype(np.float64) - a_s[j]) / (
                    1 + np.abs(a_s[j]))
                gap[row] = np.where(a_s[j] > reference.NEG / 2, g, 0).max()
    values = dict(score_gap=float(gap.max(initial=0.0)), id_mismatch=len(bad),
                  answer_faults=int(faults.sum()), unanswered=missing)
    cls = win.cls[pick]
    recall = {name: round(reference.recall(got_i[cls == c], bf_i[cls == c]), 4)
              for c, name in enumerate(win.class_names)}
    info = dict(compared=len(pick), answered=len(answered), window=len(w),
                tie_swaps=cmp["tie_swaps"], probe_near_ties=int(near.sum()),
                recall_at_k=recall)
    checks = {name: {"value": v, "limit": limits[name]}
              for name, v in values.items()}
    return dict(checks=checks, info=info,
                correct=all(v <= limits[k] for k, v in values.items()))


def percentile(x: np.ndarray, p: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``p``%
    of the values at or below it."""
    x = np.sort(np.asarray(x, np.float64))
    if len(x) == 0:
        return math.nan
    return float(x[max(int(math.ceil(p / 100.0 * len(x))) - 1, 0)])
