"""The ``topics`` deployment makes, byte for byte, what the harness made
before the deployment seam: rows, centroids, the list assignment, drawn
requests, the padded batch handed to the program and the reference's
answers, at rehearsal size, for both configurations that use it.

The digests were taken from the harness as it stood before the seam, where
``data.Corpus``, ``traffic.draw``, ``cell.direct`` and
``reference.probe_lists``/``search`` were called directly."""

import hashlib
import pathlib
import sys

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchlib.spec import Bench  # noqa: E402

SEED = 6442450949  # beyond 32 bits, as a benchmark seed may be
DIGESTS = {
    "case-study-k64": {
        "rows0": "5601d225c68bcdf6",
        "centroids": "81c3c05e8bd46ce5",
        "lists": "48b19e1a3f992bba",
        "all_rows": "b82f316c013dff44",
        "uniform.draw0": "6970439b06d3d8c3",
        "uniform.draw1": "77ac46f261065c62",
        "uniform.batch3": "51980067408b7daa",
        "uniform.probes": "df1602be9abe6cde",
        "uniform.reference": "45530596a66bbce9",
        "hot-zipf1.draw0": "14b008a5457825e8",
        "hot-zipf1.draw1": "a870274cff19ee38",
        "hot-zipf1.batch3": "8d936cb8f3ab1fef",
        "hot-zipf1.probes": "ff926f83497ae646",
        "hot-zipf1.reference": "1e19b53241b56d5f",
    },
    "case-study-k1414": {
        "rows0": "5601d225c68bcdf6",
        "centroids": "356b2f278933a2a6",
        "lists": "8458937458cc4185",
        "all_rows": "b82f316c013dff44",
        "uniform.draw0": "6970439b06d3d8c3",
        "uniform.draw1": "77ac46f261065c62",
        "uniform.batch3": "51980067408b7daa",
        "uniform.probes": "1ee29a5ccfc42bc0",
        "uniform.reference": "07bcf1406ccb85aa",
        "hot-zipf1.draw0": "14b008a5457825e8",
        "hot-zipf1.draw1": "a870274cff19ee38",
        "hot-zipf1.batch3": "8d936cb8f3ab1fef",
        "hot-zipf1.probes": "90a17e12e6ced842",
        "hot-zipf1.reference": "8e1aecf4bee20a06",
    },
}


def digest(*arrays) -> str:
    d = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        d.update(str(a.dtype).encode())
        d.update(str(a.shape).encode())
        d.update(a.tobytes())
    return d.hexdigest()[:16]


@pytest.mark.parametrize("config", sorted(DIGESTS))
def test_topics_deployment_keeps_the_bytes(config):
    bench = Bench()
    cfg = bench.config(config)
    cfg = {**cfg, **cfg["rehearsal"]}
    dep = bench.deployment(cfg).Deployment(cfg, SEED)
    core, attrs, _ = dep.data()
    got = dict(rows0=digest(*dep.corpus.rows(0)),
               centroids=digest(dep.centroids), lists=digest(dep.lists),
               all_rows=digest(core, attrs))
    t = cfg["n_probes"]
    for name in ("uniform", "hot-zipf1"):
        mix = bench.traffic(name)
        rng = np.random.default_rng([SEED, 2])
        draws = [dep.draw(mix, 256, rng, stream=2) for _ in range(2)]
        for j, r in enumerate(draws):
            got[f"{name}.draw{j}"] = digest(r.topics, r.cls, r.lo, r.hi,
                                            r.queries)
        r = draws[0]
        q, f = dep.batch(r, np.arange(3), cfg["batch"])
        got[f"{name}.batch3"] = digest(q, f.lo, f.hi, jnp.ones((1,), bool))
        rows = np.arange(64)
        probes, pv = dep.probe_lists(r.queries[rows], t)
        got[f"{name}.probes"] = digest(probes, pv)
        got[f"{name}.reference"] = digest(
            *dep.search(r, rows, probes[:, :t], cfg["top_k"]))
    assert got == DIGESTS[config]
