"""The reader of the scan kernel's fold counters, on the engine's own
rendering of known counts, and on a program without them."""

import dataclasses
import pathlib
import sys
import types

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchlib.spec import Bench  # noqa: E402


def _text(**counts):
    """metrics_text()'s engine part for an EngineStats with ``counts``."""
    from repro.core.engine import EngineStats
    from repro.core.obs import _flatten_metrics, render_prometheus

    out = {}
    _flatten_metrics(out, "engine",
                     dataclasses.asdict(EngineStats(batches=3, **counts)))
    return render_prometheus(out)


def run_of(start: str, end: str):
    snap = {k: dict(metrics_text=t) for k, t in (("start", start),
                                                  ("end", end))}
    return types.SimpleNamespace(window=types.SimpleNamespace(snap=snap))


@pytest.mark.parametrize("name", ["fold_share", "fold_share.tput"])
def test_known_counts(name):
    # 64 slots × 367 steps × k=100 per batch, two batches in the window
    start = _text(fold_passes=5_000, fold_k_passes=2_348_800)
    end = _text(fold_passes=5_000 + 185_986,
                fold_k_passes=2_348_800 + 2 * 2_348_800)
    got = Bench().reader(name)(run_of(start, end))
    assert got == pytest.approx(100.0 * 185_986 / (2 * 2_348_800))


def test_no_scan_in_the_window_reads_none():
    text = _text(fold_passes=10, fold_k_passes=100)
    assert Bench().reader("fold_share")(run_of(text, text)) is None


def test_program_without_the_counter_reads_none():
    text = ('# TYPE repro_engine_batches counter\n'
            'repro_engine_batches 3\n')
    assert Bench().reader("fold_share")(run_of(text, text)) is None
