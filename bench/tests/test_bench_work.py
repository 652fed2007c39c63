"""The scan work count behind the roofline share, and the peak table."""

import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchlib import peaks  # noqa: E402
from benchlib.work import ScanWork  # noqa: E402


def tiny():
    # K=3 lists over 6 rows, M=2 attributes, d=4, bf16 rows (8 bytes each)
    lists = np.array([0, 0, 0, 1, 1, 2])
    attrs = np.array([[1, 5], [2, 5], [3, 6], [1, 5], [9, 9], [1, 1]],
                     np.int16)
    return ScanWork(lists, attrs, n_lists=3, dim=4, row_bytes=8)


def test_work_matches_a_hand_count():
    probes = np.array([[0, 1], [1, 2]], np.int32)
    lo = np.array([[1, 5], [1, 0]], np.int16)
    hi = np.array([[2, 5], [9, 9]], np.int16)
    n_bytes, flops = tiny().batch(probes, lo, hi, size=4)
    # pairs that pass: q0 rows 0, 1 (list 0) and 3 (list 1); q1 rows 3, 4
    # (list 1) and 5 (list 2): 6 pairs of 2·d operations
    assert flops == 2 * 4 * 6
    # attributes of all 6 live rows of the probed lists (2 × int16 each)
    # plus the vectors of rows 0, 1, 3, 4, 5, each needed by some query
    assert n_bytes == 6 * 2 * 2 + 5 * 8


def test_padding_rows_and_unprobed_lists_count_nothing():
    probes = np.array([[2, 2]], np.int32)  # one query, list 2 only
    lo = np.array([[0, 0]], np.int16)
    hi = np.array([[0, 0]], np.int16)  # passes no row
    n_bytes, flops = tiny().batch(probes, lo, hi, size=4)
    assert flops == 0
    assert n_bytes == 1 * 2 * 2  # the attributes of list 2's one row


def test_least_time_takes_the_larger_bound():
    p = peaks.peaks("TPU v5 lite")
    assert peaks.least_seconds("TPU v5 lite", p["hbm_bytes_per_s"], 0) == 1.0
    assert peaks.least_seconds("TPU v5 lite", 0, p["flops_bf16"]) == 1.0
    assert peaks.least_seconds("TPU v5 lite", p["hbm_bytes_per_s"],
                               2 * p["flops_bf16"]) == 2.0


def test_unknown_device_kind_is_refused():
    with pytest.raises(KeyError, match="no peak rates"):
        peaks.least_seconds("TPU v9 imaginary", 1.0, 1.0)
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
