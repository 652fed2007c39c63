"""The readers of the program's own spans and counters: the server's stage
sums, the plan's device part and the merge's device time, on synthetic
snapshots and a small recorded trace with known answers."""

import pathlib
import sys
import types

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchlib import trace  # noqa: E402
from benchlib.cell import KERNEL  # noqa: E402
from benchlib.spec import Bench  # noqa: E402

SERVER = ("assemble_ms", "answer_wait_ms", "deliver_ms")
NEW = SERVER + ("plan_device_ms", "merge_ms")


def _text(plan_device):
    """metrics_text() with the engine's plan and plan.device stages."""
    rows = []
    for stage, (s, n) in (("plan", (1.0, 10)),
                          ("plan.device", plan_device)):
        rows += [f'repro_stage_latency_seconds_sum{{stage="{stage}"}} {s}',
                 f'repro_stage_latency_seconds_count{{stage="{stage}"}} {n}']
    return "\n".join(rows) + "\n"


def _server(batches, **sums):
    return dict(batches=batches, requests=64 * batches,
                total_latency_s=sums.get("dispatch_s", 0.0)
                + sums.get("wait_s", 0.0), **sums)


def run_of(start, end, tr=None):
    snap = {k: dict(server=s, metrics_text=t)
            for k, (s, t) in (("start", start), ("end", end))}
    return types.SimpleNamespace(
        window=types.SimpleNamespace(snap=snap, trace=tr), kernel=KERNEL)


def read(name, run):
    return Bench().reader(name)(run)


def test_readers_on_known_snapshots():
    start = (_server(10, drain_s=1.0, assemble_s=0.02, dispatch_s=0.05,
                     wait_s=1.5, deliver_s=0.004), _text((0.02, 10)))
    end = (_server(14, drain_s=1.1, assemble_s=0.028, dispatch_s=0.07,
                   wait_s=2.1, deliver_s=0.0056), _text((0.032, 14)))
    run = run_of(start, end)
    assert read("assemble_ms", run) == pytest.approx(2.0)
    assert read("answer_wait_ms", run) == pytest.approx(150.0)
    assert read("deliver_ms", run) == pytest.approx(0.4)
    assert read("plan_device_ms", run) == pytest.approx(3.0)
    # the cell's split name reads the same file
    assert read("answer_wait_ms.tput", run) == pytest.approx(150.0)


@pytest.mark.parametrize("name", NEW)
def test_no_batch_in_the_window_reads_none(name):
    snap = (_server(10, assemble_s=0.02, wait_s=1.5, deliver_s=0.004),
            _text((0.02, 10)))
    # a trace of a window in which no batch began
    assert read(name, run_of(snap, snap, tr=recorded(host=HOST[:1]))) is None


@pytest.mark.parametrize("name", NEW)
def test_program_without_the_spans_reads_none(name):
    # the server and engine of a program that lacks the stage sums and the
    # plan's parts, in an untraced run: nothing to read, and no error
    start = (dict(batches=10, requests=640, total_latency_s=1.5),
             'repro_stage_latency_seconds_sum{stage="plan"} 1.0\n')
    end = (dict(batches=14, requests=896, total_latency_s=2.1),
           'repro_stage_latency_seconds_sum{stage="plan"} 1.2\n')
    assert read(name, run_of(start, end)) is None


# Times in microseconds on the trace's clock; the window is [100, 1100).
# Batch 0: plan ops 150-160, kernel 160-400, merge ops 400-420 and
# 425-430.  Batch 1: plan 610-620, two kernel ops 620-700 and 700-900,
# merge 900-930.  An op at 50-90 falls before any batch.
DEVICE_OPS = [("prior.fusion", 50, 90), ("fusion.1", 150, 160),
              ("filtered_scan_tiled.1", 160, 400), ("sort.1", 400, 420),
              ("fusion.5", 425, 430), ("fusion.1", 610, 620),
              ("filtered_scan_tiled.1", 620, 700),
              ("filtered_scan_tiled.1", 700, 900), ("sort.1", 900, 930)]
HOST = [("bench.window", 100, 1100, None), ("bench.batch", 140, 440, 0),
        ("bench.batch", 600, 940, 1)]


def recorded(device_ops=DEVICE_OPS, host=HOST):
    from jax.profiler import ProfileData

    names = sorted({r[0] for r in device_ops + host})
    ids = {n: i + 1 for i, n in enumerate(names)}
    meta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{n}" }} }}' for n, i in ids.items())
    stat = 'stat_metadata { key: 99 value { id: 99 name: "batch" } }'

    def events(rows):
        out = []
        for name, a, b, *batch in rows:
            st = (f" stats {{ metadata_id: 99 int64_value: {batch[0]} }}"
                  if batch and batch[0] is not None else "")
            out.append(f"events {{ metadata_id: {ids[name]} "
                       f"offset_ps: {a * 10**6} "
                       f"duration_ps: {(b - a) * 10**6}{st} }}")
        return " ".join(out)

    text = (f'planes {{ id: 1 name: "/device:TPU:0" lines {{ id: 1 '
            f'name: "XLA Ops" timestamp_ns: 0 {events(device_ops)} }} '
            f'{meta} {stat} }} '
            f'planes {{ id: 2 name: "/host:CPU" lines {{ id: 2 '
            f'name: "python" timestamp_ns: 0 {events(host)} }} '
            f'{meta} {stat} }}')
    return trace.parse(ProfileData.from_text_proto(text))


def test_merge_ms_on_a_recorded_trace():
    run = run_of((_server(0), ""), (_server(2), ""), tr=recorded())
    # batch 0: 20 + 5 us after its kernel; batch 1: 30 us
    assert read("merge_ms", run) == pytest.approx((25e-3 + 30e-3) / 2)
    assert read("merge_ms.tput", run) == pytest.approx(27.5e-3)


def test_merge_ms_without_a_trace_or_a_kernel_reads_none():
    assert read("merge_ms", run_of((_server(0), ""), (_server(2), ""))) \
        is None
    no_kernel = [op for op in DEVICE_OPS if "filtered_scan" not in op[0]]
    run = run_of((_server(0), ""), (_server(2), ""),
                 tr=recorded(device_ops=no_kernel))
    assert read("merge_ms", run) is None
