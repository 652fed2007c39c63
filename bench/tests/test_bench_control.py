"""The control, the program's int8 lists one precision below the
configuration's bf16, fails the comparison that the program passes."""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import control  # noqa: E402
import run  # noqa: E402
from benchlib.spec import Bench  # noqa: E402


def test_program_passes_and_control_fails():
    bench = Bench()
    limit = bench.cell("k64.uniform.r80")["limits"]["score_gap"]
    cpu = run.device_info(1, True)
    sound = control.readings(bench, "k64.uniform.r80", 31, 1.0, "", cpu,
                             True)
    assert sound["correct"] is True
    assert sound["values"]["score_gap"] <= limit
    ctl = control.readings(bench, "k64.uniform.r80", 31, 1.0, "sq8", cpu,
                           True)
    assert ctl["correct"] is False
    assert ctl["values"]["score_gap"] > 3 * limit
    assert ctl["values"]["id_mismatch"] > 0
