"""BENCHMARK.json keeps to its contract, and a new configuration, traffic
mix, cell or metric needs only new files."""

import hashlib
import json
import pathlib
import re
import shutil
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import run  # noqa: E402
from benchlib.spec import Bench  # noqa: E402

ROOT = run.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def spec():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_benchmark_json_keeps_to_its_contract():
    s = spec()
    assert list(s) == ["command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"]
    assert s["paths"] == ["bench"] and s["command"][1] == "bench/run.py"
    names = [c["name"] for c in s["configs"]] + [w["name"] for w in
                                                 s["workloads"]]
    metrics = s["end_to_end"] + s["per_layer"]
    names += [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    assert all(NAME.match(n) for n in names)
    for c in s["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(cfg["reduced"])
    for w in s["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        assert (ROOT / "bench/traffic" / f"{w['traffic']}.json").exists()
        assert (ROOT / "bench/cells" / f"{w['name']}.json").exists()
    bench = Bench()
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert callable(bench.reader(m["name"]))
    e2e = {m["name"]: m for m in s["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    assert all(m["source"] in ("host_clock", "device_trace")
               for m in e2e.values())
    assert all(m["moves"] in e2e for m in s["per_layer"])
    # a full check of 24 cells fits its allowance
    rs = s["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def _digest(root: pathlib.Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_config_mix_cell_and_metric_need_only_new_files(tmp_path, capsys):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(tmp_path / "bench")
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs/case-study-k64.json").read_text())
    cfg.update(name="narrow-k16", n_lists=16,
               rehearsal={**cfg["rehearsal"], "n_lists": 16})
    (b / "configs/narrow-k16.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic/uniform.json").read_text())
    mix["classes"] = [{"name": "unfiltered", "share": 1.0, "predicates": []}]
    (b / "traffic/unfiltered.json").write_text(json.dumps(mix))
    (b / "cells/k16.unfiltered.json").write_text(
        (b / "cells/k64.uniform.r80.json").read_text())
    (b / "metrics/answered.py").write_text(
        '"""answered (client): answers that came back."""\n\n'
        "import numpy as np\n\n\n"
        "def read(run):\n"
        "    return int((~np.isnan(run.window.done)).sum())\n")
    s = json.loads((tmp_path / "BENCHMARK.json").read_text())
    s["configs"].append(dict(s["configs"][0], name="narrow-k16",
                             file="bench/configs/narrow-k16.json"))
    s["workloads"].append(dict(name="k16.unfiltered", config="narrow-k16",
                               traffic="unfiltered", chips=1, why="test"))
    for m in s["end_to_end"]:
        if m["name"] == "p95_ms":
            m["workloads"].append("k16.unfiltered")
    s["per_layer"].append(dict(name="answered", unit="requests",
                               better="higher", source="host_clock",
                               layer="client", moves="p95_ms",
                               workloads=["k16.unfiltered"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(s))

    after = _digest(b)
    assert all(after[f] == h for f, h in before.items())  # nothing edited
    bench = Bench(root=tmp_path, bench=b)
    args = run.parse_args(["--workload", "k16.unfiltered", "--seed", "5",
                           "--seconds", "1", "--trace", "1",
                           "--cpu-rehearsal"])
    res = run.run(bench, bench.workload("k16.unfiltered"), args,
                  run.device_info(1, True))
    assert res["correct"] is True
    # the new cell reports the new metric, and none listed for other cells
    assert set(res["metrics"]) == {"answered"}
    assert res["metrics"]["answered"]["value"] > 0


def test_split_metric_is_read_by_its_base_reader():
    bench = Bench()
    split = [m["name"] for m in spec()["per_layer"] if "." in m["name"]]
    assert split
    for name in split:
        base = name.split(".", 1)[0]
        assert bench.reader(name).__code__.co_filename.endswith(f"/{base}.py")
