"""Spans and counters of the serving path (``repro.core.obs``): the span's
sink, the Prometheus rendering, the server's stage sums and the spans in a
profiler trace."""

import glob
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import HybridSpec, build_ivf
from repro.core.obs import (StageHistogram, _flatten_metrics,
                            render_prometheus, render_stage_histograms, span)
from repro.core.serving import SearchServer, make_fused_search_fn

SERVER_SUMS = ("drain_s", "assemble_s", "dispatch_s", "wait_s", "deliver_s")
PLAN_PARTS = ("repro.engine.plan.prep", "repro.engine.plan.device",
              "repro.engine.plan.tables")


def test_span_hands_its_seconds_to_the_sink():
    got = []
    with span("test.sleep", got.append, batch=3) as s:
        time.sleep(0.01)
    assert len(got) == 1 and got[0] >= 0.01 and got[0] == s.seconds
    with pytest.raises(RuntimeError):  # the sink runs when the block raises
        with span("test.raise", got.append):
            raise RuntimeError("x")
    assert len(got) == 2 and got[1] >= 0.0
    with span("test.nosink", None) as s:  # no sink: read .seconds after
        pass
    assert s.seconds >= 0.0


# The rendering as it was before it moved into obs.py, on a fixed input.
_FLAT = {"engine.batches": 3, "engine.hit_rate": 0.5,
         "engine.pipeline": 'on "x" \\', "engine.ok": True,
         "engine.none": None, "engine.nested.hits": 7,
         "engine.nested.obj": "(1, 2)"}
_PROM = (
    '# TYPE repro_engine_batches counter\nrepro_engine_batches 3\n'
    '# TYPE repro_engine_hit_rate gauge\nrepro_engine_hit_rate 0.5\n'
    '# TYPE repro_engine_nested_hits counter\nrepro_engine_nested_hits 7\n'
    '# TYPE repro_engine_nested_obj gauge\n'
    'repro_engine_nested_obj{value="(1, 2)"} 1\n'
    '# TYPE repro_engine_ok gauge\nrepro_engine_ok 1\n'
    '# TYPE repro_engine_pipeline gauge\n'
    'repro_engine_pipeline{value="on \\"x\\" \\\\"} 1\n')


def _hist_text(stage, buckets, inf, total):
    edges = ("0.0005", "0.001", "0.0025", "0.005", "0.01", "0.025", "0.05",
             "0.1", "0.25", "0.5", "1.0", "2.5")
    name = "repro_stage_latency_seconds"
    rows = [f'{name}_bucket{{stage="{stage}",le="{e}"}} {c}'
            for e, c in zip(edges, buckets)]
    rows.append(f'{name}_bucket{{stage="{stage}",le="+Inf"}} {inf}')
    rows.append(f'{name}_sum{{stage="{stage}"}} {total}')
    rows.append(f'{name}_count{{stage="{stage}"}} {inf}')
    return "\n".join(rows)


def test_prometheus_rendering_is_unchanged_by_the_move():
    flat = {}
    _flatten_metrics(flat, "engine", {
        "batches": 3, "hit_rate": np.float32(0.5),
        "pipeline": 'on "x" \\', "ok": True, "none": None,
        "nested": {"hits": np.int64(7), "obj": (1, 2)}})
    assert flat == _FLAT
    assert render_prometheus(flat) == _PROM
    assert (render_prometheus({"3x.y-z": 1.5, "a.misses": 2}, prefix="p")
            == "# TYPE p_3x_y_z gauge\np_3x_y_z 1.5\n"
               "# TYPE p_a_misses counter\np_a_misses 2\n")
    hists = {"plan": StageHistogram(), "fetch": StageHistogram()}
    for v in (0.0003, 0.004, 0.004, 3.0):
        hists["plan"].observe(v)
    hists["fetch"].observe(0.02)
    assert render_stage_histograms(hists) == "\n".join((
        "# TYPE repro_stage_latency_seconds histogram",
        _hist_text("fetch", (0,) * 5 + (1,) * 7, 1, 0.02),
        _hist_text("plan", (1, 1, 1) + (3,) * 9, 4, 3.0083))) + "\n"
    assert render_stage_histograms({}) == ""


@pytest.fixture(scope="module")
def served():
    rng = np.random.default_rng(0)
    n, d, m = 600, 12, 3
    core = rng.standard_normal((n, d)).astype(np.float32)
    core /= np.linalg.norm(core, axis=-1, keepdims=True)
    attrs = rng.integers(0, 5, (n, m)).astype(np.int16)
    index, _ = build_ivf(
        jax.random.key(0), HybridSpec(dim=d, n_attrs=m,
                                      core_dtype=jnp.float32),
        core, attrs, n_clusters=6, kmeans_mode="lloyd", kmeans_steps=4)
    fn = make_fused_search_fn(index, k=5, n_probes=3, q_block=8,
                              backend="xla")
    server = SearchServer(fn, batch_size=8, dim=d, n_attrs=m, n_terms=1,
                          n_shards=1)
    server.start()
    # compile every shape the tests below send before anything is timed
    server.search_blocking(core[0], timeout=120)
    yield server, core
    server.stop()
    fn.close()


def test_server_stage_sums_and_batch_numbers(served):
    server, core = served
    before = dict(server.stats)
    batches = [server.search_blocking(core[i], timeout=60).batch
               for i in range(5)]
    futs = [server.submit(core[i]) for i in range(12)]
    resps = [f.get(timeout=60) for f in futs]
    st = server.stats
    assert all(b1 > b0 for b0, b1 in zip(batches, batches[1:]))
    assert min(r.batch for r in resps) > batches[-1]
    assert st["batches"] - before["batches"] == len(
        set(batches) | {r.batch for r in resps})
    for key in SERVER_SUMS:
        assert st[key] > before[key], key
    # both sums and the total move in one step, from the same clock reads
    assert st["dispatch_s"] + st["wait_s"] == st["total_latency_s"]


def test_stats_copies_keep_total_equal_to_dispatch_plus_wait(served):
    # readers copy stats from other threads (the benchmark's snapshots, the
    # operator scrape); with a short switch interval a copy taken between
    # two separate updates would catch the sums apart
    server, core = served
    copies, stop = [], threading.Event()

    def reader():
        while not stop.is_set():
            copies.append(dict(server.stats))

    t = threading.Thread(target=reader, daemon=True)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        t.start()
        futs = [server.submit(core[i % len(core)]) for i in range(64)]
        for f in futs:
            f.get(timeout=60)
    finally:
        stop.set()
        t.join(timeout=30)
        sys.setswitchinterval(interval)
    assert not t.is_alive() and copies
    assert all(c["total_latency_s"] == c["dispatch_s"] + c["wait_s"]
               for c in copies)


def _trace_events(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append((e.name, e.start_ns, e.end_ns,
                                {k: v for k, v in e.stats}))
    return sorted(out, key=lambda e: e[1])


def test_spans_in_a_profiler_trace(served, tmp_path):
    server, core = served
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(4):
            server.search_blocking(core[i], timeout=60)
    finally:
        jax.profiler.stop_trace()
    ev = _trace_events(tmp_path)
    names = {e[0] for e in ev}
    assert {f"repro.server.{s[:-2]}" for s in SERVER_SUMS} <= names
    assert {"repro.engine.plan", *PLAN_PARTS} <= names
    dispatch = [e for e in ev if e[0] == "repro.server.dispatch"]
    assert all("batch" in e[3] for e in dispatch)
    plans = [e for e in ev if e[0] == "repro.engine.plan"]
    assert len(plans) >= 4
    for _, a, b, _ in plans:
        # the plan runs inside the server's call into the search function
        assert any(d[1] <= a and b <= d[2] for d in dispatch)
        # and its three parts tile it, in order: between them lies only
        # the spans' own entry and exit (tens of microseconds)
        parts = [e for e in ev if e[0] in PLAN_PARTS and a <= e[1] < b]
        assert [e[0] for e in parts] == list(PLAN_PARTS)
        edges = [a] + [t for e in parts for t in e[1:3]] + [b]
        gaps = [y - x for x, y in zip(edges[::2], edges[1::2])]
        assert all(0 <= g < 2e6 for g in gaps), gaps
