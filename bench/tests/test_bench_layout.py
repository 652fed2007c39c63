"""BENCHMARK.json keeps to its contract, and a new deployment,
configuration, traffic mix, cell or metric needs only new files."""

import hashlib
import json
import pathlib
import re
import shutil
import sys
import tempfile

import numpy as np
import pytest

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
        assert (ROOT / "bench/deployments"
                / f"{cfg['deployment']}.py").is_file()
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


def _copy_bench(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path / "bench"


def _id_altered(queries, fspec, out):
    s, i = (np.array(x) for x in out)
    i[0, 0] = i[0, 0] + 1 if i[0, 0] >= 0 else i[0, 0]
    return s, i


def test_second_deployment_needs_only_new_files(tmp_path, monkeypatch):
    """A deployment that differs from ``topics`` in metric (l2), tier (disk,
    under a resident budget below the index's bytes) and filters (two DNF
    terms) comes as a module, a config, a mix and a cell, all new."""
    tmp = tmp_path / "tmp"
    tmp.mkdir()
    monkeypatch.setattr(tempfile, "tempdir", str(tmp))  # index and trace
    b = _copy_bench(tmp_path)
    before = _digest(b)
    shutil.copy(pathlib.Path(__file__).with_name("deployment_dnf_l2_disk.py"),
                b / "deployments/dnf_l2_disk.py")
    cfg = json.loads((b / "configs/case-study-k1414.json").read_text())
    cfg.update(name="dnf-l2-disk", deployment="dnf_l2_disk", tier="disk",
               metric="l2")
    (b / "configs/dnf-l2-disk.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic/uniform.json").read_text())
    eq = lambda a: {"attr": a, "op": "eq"}  # noqa: E731
    window = lambda a, w: {"attr": a, "op": "window", "width": w}  # noqa
    mix["classes"] = [
        {"name": "unfiltered", "share": 0.25, "terms": [[]]},
        {"name": "one-of-two", "share": 0.25, "terms": [[eq(1)], [eq(1)]]},
        {"name": "narrow-or-narrow", "share": 0.5,
         "terms": [[eq(1), window(0, 1000)],
                   [{"attr": 2, "op": "eq", "value": 0}, window(3, 100)]]}]
    (b / "traffic/dnf2.json").write_text(json.dumps(mix))
    (b / "cells/l2disk.dnf2.json").write_text(
        (b / "cells/k1414.hot.over.json").read_text())
    s = json.loads((tmp_path / "BENCHMARK.json").read_text())
    s["configs"].append(dict(s["configs"][1], name="dnf-l2-disk",
                             file="bench/configs/dnf-l2-disk.json"))
    s["workloads"].append(dict(name="l2disk.dnf2", config="dnf-l2-disk",
                               traffic="dnf2", chips=1, why="test"))
    for m in s["end_to_end"] + s["per_layer"]:
        if m["name"] in ("qps", "batch_fill.tput"):
            m["workloads"].append("l2disk.dnf2")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(s))
    after = _digest(b)
    assert all(after[f] == h for f, h in before.items())  # nothing edited

    bench = Bench(root=tmp_path, bench=b)
    cell = bench.workload("l2disk.dnf2")
    argv = ["--workload", "l2disk.dnf2", "--seed", "6442450951",
            "--seconds", "1", "--cpu-rehearsal", "--trace"]
    res = run.run(bench, cell, run.parse_args(argv + ["1"]),
                  run.device_info(1, True))
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {"batch_fill.tput"}
    bad = run.run(bench, cell, run.parse_args(argv + ["0"]),
                  run.device_info(1, True), fault=_id_altered)
    assert bad["correct"] is False
    assert bad["checks"]["id_mismatch"]["value"] + bad["checks"][
        "answer_faults"]["value"] > 0
    assert not list(tmp.iterdir())  # the index and the trace are removed


@pytest.mark.parametrize("key,value", [("tier", "disk"), ("metric", "l2"),
                                       ("store_dtype", "int8")])
def test_topics_refuses_what_it_does_not_run(key, value):
    bench = Bench()
    cfg = bench.config("case-study-k64")
    with pytest.raises(ValueError, match=key):
        bench.deployment(cfg).Deployment({**cfg, key: value}, 1)


def test_missing_deployment_is_an_error():
    with pytest.raises(FileNotFoundError, match="no-such"):
        Bench().deployment({"name": "x", "deployment": "no-such"})
