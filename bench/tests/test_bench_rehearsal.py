"""CPU rehearsals of whole runs: the result line and the refusals."""

import json
import os
import pathlib
import shutil
import subprocess
import sys
import tempfile

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import run  # noqa: E402

ROOT = run.ROOT
# a seed beyond 32 bits, as the driver's are
ARGS = ["--seed", "6442450949", "--seconds", "1", "--cpu-rehearsal"]


def last_line(capsys, argv, **kw):
    rc = run.main(argv, **kw)
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)


def test_rehearsal_result_line(capsys):
    rc, res = last_line(capsys, ["--workload", "k64.uniform.r80", "--trace",
                                 "0"] + ARGS)
    assert rc == 0
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] >= 1
    assert isinstance(res["device"]["memory_peak_bytes"], int)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(res["metrics"]) == {
        m["name"] for m in spec["end_to_end"]
        if "k64.uniform.r80" in m.get("workloads", ["k64.uniform.r80"])}
    for m in res["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]


def test_rehearsal_traced_run(capsys, tmp_path, monkeypatch):
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    rc, res = last_line(capsys, ["--workload", "k64.uniform.r80", "--trace",
                                 "1"] + ARGS)
    assert rc == 0 and res["correct"] is True
    # the trace went to a directory of the run's own, removed after
    assert not list(tmp_path.iterdir())
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    per_layer = {m["name"] for m in spec["per_layer"]}
    # host-side metrics are read; device metrics stay silent off the chip
    assert {"send_late_ms", "queue_ms", "batch_fill", "batch_ms", "plan_ms",
            "scan_slots"} <= set(res["metrics"]) <= per_layer
    assert not {"scan_ms", "filtered_scan_tiled_roofline",
                "device_idle"} & set(res["metrics"])
    dev = res["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(res["breakdown"]["device_ops"]) <= 10


def test_no_tpu_means_no_result(capsys):
    rc, res = last_line(capsys, ["--workload", "k64.uniform.r80", "--seed",
                                 "1", "--seconds", "1", "--trace", "0"])
    assert rc != 0 and res is None


def test_checkout_without_the_program_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "k64.uniform.r80"]
        + ARGS, cwd=tmp_path, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
