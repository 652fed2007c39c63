"""``correct`` comes out false when the timed path is broken underneath.

Each fault is planted where the answers are produced, between the search
function and the server, and the rest of a CPU rehearsal runs as usual.
"""

import json
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import run  # noqa: E402

NEG = -3.0e38
ARGS = ["--workload", "k64.uniform.r80", "--seed", "977", "--seconds", "1",
        "--trace", "0", "--cpu-rehearsal"]


def _real(queries) -> int:
    """Requests in the batch; the server pads with zero queries."""
    return int((np.abs(np.asarray(queries)).sum(axis=1) > 0).sum())


def answer_altered(queries, fspec, out):
    s, i = (np.array(x) for x in out)
    i[0, 0] = i[0, 0] + 1 if i[0, 0] >= 0 else i[0, 0]
    return s, i


def half_batch_left_out(queries, fspec, out):
    s, i = (np.array(x) for x in out)
    b = _real(queries)
    s[b // 2:b], i[b // 2:b] = NEG, -1
    return s, i


def answers_misrouted(queries, fspec, out):
    s, i = (np.array(x) for x in out)
    b = _real(queries)
    s[:b], i[:b] = np.roll(s[:b], 1, axis=0), np.roll(i[:b], 1, axis=0)
    return s, i


@pytest.mark.parametrize("fault", [answer_altered, half_batch_left_out,
                                   answers_misrouted],
                         ids=lambda f: f.__name__)
def test_fault_is_not_correct(capsys, fault):
    rc = run.main(ARGS, fault=fault)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
