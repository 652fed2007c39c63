"""The trace reduction, on a small recorded trace with known answers."""

import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchlib import trace  # noqa: E402

# Times in microseconds on the trace's clock.  The window is [100, 1100).
# Device ops: a kernel of batch 0 from 150 to 450, a merge 450-500, a
# kernel of batch 1 from 700 to 1000 that overlaps a copy 950-1050, and an
# op before the window (50-120) that belongs to no batch begun inside it.
DEVICE_OPS = [("prior.fusion", 50, 120), ("filtered_scan_tiled.1", 150, 450),
              ("merge.3", 450, 500), ("filtered_scan_tiled.1", 700, 1000),
              ("copy.7", 950, 1050)]
HOST = [("bench.window", 100, 1100, None), ("bench.batch", 140, 520, 0),
        ("bench.plan", 140, 148, None), ("bench.batch", 690, 1060, 1),
        ("bench.plan", 690, 698, None)]


def _events(rows, stat_id):
    out = []
    for name, a, b, *batch in rows:
        stat = (f" stats {{ metadata_id: {stat_id} int64_value: {batch[0]} }}"
                if batch and batch[0] is not None else "")
        out.append(f"events {{ metadata_id: {{{name}}} offset_ps: {a * 10**6}"
                   f" duration_ps: {(b - a) * 10**6}{stat} }}")
    return out


def recorded():
    from jax.profiler import ProfileData

    names = sorted({r[0] for r in DEVICE_OPS + HOST})
    ids = {n: i + 1 for i, n in enumerate(names)}
    meta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                    for n, i in ids.items())
    stat = 'stat_metadata { key: 99 value { id: 99 name: "batch" } }'

    def fill(rows):
        return " ".join(e.replace("{" + n + "}", str(ids[n]))
                        for e, n in zip(_events(rows, 99), (r[0] for r in rows)))

    text = (f'planes {{ id: 1 name: "/device:TPU:0" '
            f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {fill(DEVICE_OPS)} }} '
            f'{meta} {stat} }} '
            f'planes {{ id: 2 name: "/host:CPU" '
            f'lines {{ id: 2 name: "python" timestamp_ns: 0 {fill(HOST)} }} '
            f'{meta} {stat} }}')
    return trace.parse(ProfileData.from_text_proto(text))


def test_busy_and_window():
    tr = recorded()
    assert tr.window_s == pytest.approx(1000e-6)
    # union inside the window: 100-120, 150-500, 700-1050
    assert trace.busy_s(tr) == pytest.approx((20 + 350 + 350) * 1e-6)


def test_kernel_time_by_batch():
    per = trace.kernel_time_by_batch(recorded(), r"filtered_scan")
    assert per == pytest.approx({0: 300e-6, 1: 300e-6})


def test_idle_gaps_are_labelled_by_host_span():
    tr = recorded()
    label = trace.host_label(tr, pending_at=lambda t: 1 if t > 1e6 else 0)
    gaps = trace.idle_gaps(tr, label)
    # 120-150 and 500-700 fall between batch spans with nothing queued;
    # 1050-1100 falls after batch 1's span with a request waiting
    assert [(a / 1e3, b / 1e3, lab) for a, b, lab in gaps] == [
        (120, 150, "arrival wait"), (500, 700, "arrival wait"),
        (1050, 1100, "batch assembly")]
    assert label(505e3, 515e3) == "batch"
    assert label(141e3, 147e3) == "plan"


def test_breakdown_shape():
    b = trace.breakdown(recorded(), lambda t: 0)
    assert b["device_ops"][0] == ["filtered_scan_tiled.1", pytest.approx(600e-6)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["idle_gaps"][0][1] == pytest.approx(200e-6)


def test_trace_without_window_span_is_refused():
    from jax.profiler import ProfileData

    with pytest.raises(ValueError):
        trace.parse(ProfileData.from_text_proto('planes { id: 1 name: "/host:CPU" }'))
