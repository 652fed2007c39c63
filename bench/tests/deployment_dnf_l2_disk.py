"""A second deployment, which the layout test copies into a copy of
``bench/deployments/`` to show that a deployment needs only new files.

It serves the ``topics`` rows (``benchlib/data.py``) in another way on
three axes the program has: the l2 metric (scores are minus the squared
distance), the disk tier (the index saved by ``save_index`` under
``TMPDIR`` and served with a resident budget of a quarter of its bytes),
and filters of two DNF terms (a row passes if it lies inside every bound
of either term).  Its reference is written out in NumPy, in float64.

A mix's class gives its filter as ``"terms"``: one or two conjunctions of
``{"attr", "op": "eq"|"window", ...}``; a class with one term voids the
other.
"""

from __future__ import annotations

import dataclasses
import shutil
import tempfile

import jax.numpy as jnp
import numpy as np

from benchlib import traffic
from benchlib.data import Corpus

IMPLEMENTED = {"tier": "disk", "metric": "l2", "store_dtype": "bfloat16"}
CONTROL = "sq8"
N_TERMS = 2
ATTR_MIN, ATTR_MAX = -32768, 32767
NEG = -3.0e38


def term_bounds(terms, card, rng):
    """[N_TERMS, M] int16 bounds of a class's terms."""
    lo = np.full((N_TERMS, len(card)), ATTR_MAX, np.int64)  # void terms
    hi = np.full((N_TERMS, len(card)), ATTR_MIN, np.int64)
    for t, preds in enumerate(terms):
        lo[t], hi[t] = ATTR_MIN, ATTR_MAX
        for p in preds:
            a = p["attr"]
            if p["op"] == "eq":
                v = (p["value"] if "value" in p
                     else int(rng.integers(0, card[a])))
                lo[t, a], hi[t, a] = v, v
            elif p["op"] == "window":
                s = int(rng.integers(0, card[a] - p["width"]))
                lo[t, a], hi[t, a] = s, s + p["width"] - 1
            else:
                raise ValueError(f"unknown predicate op {p['op']!r}")
    return lo.astype(np.int16), hi.astype(np.int16)


def passes(attrs, lo, hi):
    """[n, N] bool: row passes some term of query's [n, T, M] bounds."""
    out = np.zeros((len(lo), len(attrs)), bool)
    for t in range(lo.shape[1]):
        out |= ((attrs[None] >= lo[:, t, None])
                & (attrs[None] <= hi[:, t, None])).all(-1)
    return out


def top_k(scores, k):
    """Largest ``k`` per row, ties to the lower index: (values, ids)."""
    order = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(scores, order, axis=1), order


@dataclasses.dataclass
class Requests(traffic.Requests):
    lo: np.ndarray  # [n, N_TERMS, M] int16
    hi: np.ndarray


class Deployment:
    def __init__(self, cfg: dict, seed: int):
        for key, value in IMPLEMENTED.items():
            if cfg.get(key) != value:
                raise ValueError(f"runs {key}={value!r}, not "
                                 f"{cfg.get(key)!r}")
        self.cfg = cfg
        self.corpus = Corpus(cfg, seed)
        self.n_rows = self.corpus.n_rows
        self.dir = None

    def data(self):
        self.centroids = self.corpus.centroids()
        core, attrs, lists = self.corpus.all_rows(self.centroids)
        self.lists, self.attrs = np.asarray(lists), np.asarray(attrs)
        self.counts = np.bincount(self.lists, minlength=self.cfg["n_lists"])
        return core, attrs, lists

    def build(self, variant: str = ""):
        from repro.core import HybridSpec
        from repro.core.ivf import build_from_assignments, quantize_index
        from repro.core.serving import make_fused_search_fn
        from repro.core.storage import save_index

        cfg = self.cfg
        core, attrs, lists = self.data()
        index, stats = build_from_assignments(
            HybridSpec(cfg["dim"], cfg["n_attrs"], metric="l2"),
            self.centroids, core, attrs, lists)
        if variant == CONTROL:
            index = quantize_index(index)
        elif variant:
            raise ValueError(f"unknown variant {variant!r}")
        if stats.n_dropped:
            raise RuntimeError(f"build dropped {stats.n_dropped} rows")
        self.dir = tempfile.mkdtemp(prefix="bench-index-")
        save_index(index, self.dir)
        budget = index.nbytes() // 4
        sizes = dict(rows=self.n_rows, index_bytes=index.nbytes(),
                     resident_budget=budget)
        del index, core, attrs, lists
        fn = make_fused_search_fn(self.dir, k=cfg["top_k"],
                                  n_probes=cfg["n_probes"],
                                  q_block=cfg["batch"],
                                  resident_budget_bytes=budget)
        sizes.update(backend=fn.engine.backend,
                     resident_bytes=int(fn.index.resident_bytes()))
        return fn, dict(dim=cfg["dim"], n_attrs=cfg["n_attrs"],
                        n_terms=N_TERMS, n_shards=1), sizes

    def free(self):
        if self.dir is not None:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir = None

    def draw(self, mix: dict, n: int, rng, stream: int) -> Requests:
        card = self.cfg["attr_cardinality"]
        classes = mix["classes"]
        cls = traffic.classes(mix, n, rng)
        lo = np.empty((n, N_TERMS, len(card)), np.int16)
        hi = np.empty_like(lo)
        for i, c in enumerate(cls):
            lo[i], hi[i] = term_bounds(classes[c]["terms"], card, rng)
        topics = traffic.topics(mix, n, self.cfg["n_topics"], rng)
        return Requests(self.corpus.queries(topics, stream), cls,
                        [c["name"] for c in classes], lo, hi)

    def submit_args(self, r: Requests, i: int):
        return r.queries[i], (r.lo[i], r.hi[i])

    def batch(self, r: Requests, rows, size: int):
        from repro.core.filters import FilterSpec

        b = len(rows)
        qq = np.zeros((size, self.cfg["dim"]), np.float32)
        ll = np.zeros((size,) + r.lo.shape[1:], np.int16)
        hh = np.zeros_like(ll)
        qq[:b], ll[:b], hh[:b] = r.queries[rows], r.lo[rows], r.hi[rows]
        return jnp.asarray(qq), FilterSpec(lo=jnp.asarray(ll),
                                           hi=jnp.asarray(hh))

    def probe_lists(self, queries: np.ndarray, t: int):
        """The ``t + 1`` nearest lists that hold rows: ``2 q·c - |c|²``."""
        c = np.asarray(self.centroids, np.float64)
        s = 2.0 * queries.astype(np.float64) @ c.T - (c * c).sum(-1)
        vals, ids = top_k(np.where(self.counts[None, :] > 0, s, NEG), t + 1)
        return ids, vals

    def _rows(self) -> np.ndarray:
        return np.concatenate([np.asarray(self.corpus.rows(i)[0], np.float64)
                               for i in range(self.corpus.n_chunks)])

    def search(self, r: Requests, rows, probes: np.ndarray, k: int):
        x, q = self._rows(), r.queries[rows].astype(np.float64)
        s = -((q * q).sum(-1)[:, None] - 2.0 * q @ x.T
              + (x * x).sum(-1)[None, :])
        ok = passes(self.attrs, r.lo[rows], r.hi[rows])
        member = np.zeros((len(q), self.cfg["n_lists"]), bool)
        np.put_along_axis(member, probes, True, axis=1)
        ivf_s, ivf_i = top_k(np.where(ok & member[:, self.lists], s, NEG), k)
        bf_s, bf_i = top_k(np.where(ok, s, NEG), k)
        return (ivf_s, np.where(ivf_s > NEG / 2, ivf_i, -1),
                np.where(bf_s > NEG / 2, bf_i, -1))

    def answer_faults(self, ids: np.ndarray, r: Requests, rows):
        live = ids >= 0
        out = ((ids >= self.n_rows) | (ids < -1)).any(axis=1)
        out |= (~live[:, :-1] & live[:, 1:]).any(axis=1)
        a = self.attrs[np.clip(ids, 0, self.n_rows - 1)]  # [n, k, M]
        lo, hi = r.lo[rows][:, :, None], r.hi[rows][:, :, None]
        ok = ((a[:, None] >= lo) & (a[:, None] <= hi)).all(-1).any(1)
        out |= (live & ~ok).any(axis=1)
        srt = np.sort(np.where(live, ids, -1 - np.arange(ids.shape[1])),
                      axis=1)
        return out | (srt[:, 1:] == srt[:, :-1]).any(axis=1)

    def work(self, r: Requests, rows, size: int):
        cfg = self.cfg
        probes, _ = self.probe_lists(r.queries[rows], cfg["n_probes"])
        member = np.zeros((len(rows), cfg["n_lists"]), bool)
        np.put_along_axis(member, probes[:, :cfg["n_probes"]], True, axis=1)
        pairs = passes(self.attrs, r.lo[rows], r.hi[rows]) & member[
            :, self.lists]
        attr_bytes = self.counts[member.any(0)].sum() * cfg["n_attrs"] * 2
        vec_bytes = pairs.any(0).sum() * cfg["dim"] * 2
        return float(attr_bytes + vec_bytes), 2.0 * cfg["dim"] * pairs.sum()
