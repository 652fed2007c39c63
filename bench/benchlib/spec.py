"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

- ``bench/configs/<config>.json``: the configuration's sizes and source;
- ``bench/traffic/<traffic>.json``: the traffic mix's parameters;
- ``bench/cells/<workload>.json``: the cell's offered rate and its checks;
- ``bench/metrics/<metric>.py``: one reader per metric, ``read(run)``;
  ``<base>.<part>`` falls back to ``<base>.py``.

Adding a configuration, a mix, a cell or a metric adds files; no file that
exists changes.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]


class Bench:
    def __init__(self, root: pathlib.Path = BENCH.parent,
                 bench: pathlib.Path = BENCH):
        self.root, self.dir = pathlib.Path(root), pathlib.Path(bench)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def _json(self, kind: str, name: str) -> dict:
        return json.loads((self.dir / kind / f"{name}.json").read_text())

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def cell(self, name: str) -> dict:
        return self._json("cells", name)

    def metrics(self, workload: str, trace: bool):
        """The metrics a run of ``workload`` reports: the end-to-end ones
        without a trace, the per-layer ones with it."""
        e2e = self.spec["end_to_end"]
        reported = {m["name"] for m in e2e
                    if workload in m.get("workloads", [workload])}
        if not trace:
            return [m for m in e2e if m["name"] in reported]
        return [m for m in self.spec["per_layer"]
                if workload in m.get("workloads", [workload])
                and m["moves"] in reported]

    def reader(self, metric: str):
        """The ``read(run)`` function of ``bench/metrics/<metric>.py``.  A
        quantity split by the end-to-end metric it moves, ``<base>.<part>``
        (``batch_ms.tput``), is read by ``<base>.py`` unless it has a file
        of its own."""
        path = self.dir / "metrics" / f"{metric}.py"
        base = self.dir / "metrics" / f"{metric.split('.', 1)[0]}.py"
        if not path.exists() and base.exists():
            path = base
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{metric.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
