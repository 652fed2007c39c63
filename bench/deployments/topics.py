"""The ``topics`` deployment: the paper's case study (arXiv 2501.13442 §5).

Rows are unit 768-d bf16 vectors around random topic centres, with int16
attributes drawn independently of the content (``benchlib/data.py``); the
benchmark's own partition (spherical k-means on a fixed sample) is handed
to the program by ``build_from_assignments``.  The program serves the
index from RAM under the dot product through ``make_fused_search_fn``,
one conjunctive filter term per request.  The reference
(``benchlib/reference.py``) and the roofline's work count
(``benchlib/work.py``) hold the same semantics.

A deployment module holds everything a configuration's deployment decides
and the rest of the harness does not (``benchlib/spec.py::deployment``):
``CONTROL``, the ``variant`` that ``control.py`` builds as the control, and
one class ``Deployment(cfg, seed)``, which refuses a configuration value it
does not implement, with

- ``build(variant)``: the data from the seed and the program on it:
  ``(search_fn, server_args, sizes)``, where ``server_args`` are
  ``SearchServer``'s keyword arguments besides its batch size; ``free()``
  drops what it made besides the search function;
- ``draw(mix, n, rng, stream)``: ``n`` requests of a mix
  (``traffic.Requests`` with the deployment's filter payload);
  ``submit_args(requests, i)``: what ``SearchServer.submit`` takes for one;
  ``batch(requests, rows, size)``: what the search function takes for
  those rows padded to ``size``;
- ``probe_lists(queries, t)``, ``search(requests, rows, probes, k)``: the
  reference's probed lists with their scores, and its top-k over those
  lists and over every row;
- ``answer_faults(ids, requests, rows)``: answers that break the payload;
- ``work(requests, rows, size)``: the scan's bytes and operations for one
  batch, for the roofline.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import reference, traffic
from benchlib.data import Corpus
from benchlib.work import ScanWork

# what this deployment runs; any other value of these keys is refused
IMPLEMENTED = {"tier": "ram", "metric": "dot", "store_dtype": "bfloat16"}
ATTR_MIN, ATTR_MAX = -32768, 32767  # the int16 attribute range
# the build of the program that serves as the control (``control.py``): its
# own int8 (SQ8) lists, one precision below the configuration's bf16
CONTROL = "sq8"


def bounds(preds, card, rng):
    """A conjunction of ``{"attr", "op", ...}`` as int16 bounds [M]: ``op``
    one of ``eq`` (``value``, or a value drawn uniformly below the
    attribute's cardinality), ``le``, ``ge``, and ``window`` (``width``
    consecutive values at a random start)."""
    m = len(card)
    lo = np.full(m, ATTR_MIN, np.int64)
    hi = np.full(m, ATTR_MAX, np.int64)
    for p in preds:
        a, op = p["attr"], p["op"]
        if op == "eq":
            v = p["value"] if "value" in p else int(rng.integers(0, card[a]))
            lo[a], hi[a] = max(lo[a], v), min(hi[a], v)
        elif op == "le":
            hi[a] = min(hi[a], p["value"])
        elif op == "ge":
            lo[a] = max(lo[a], p["value"])
        elif op == "window":
            s = int(rng.integers(0, card[a] - p["width"]))
            lo[a], hi[a] = max(lo[a], s), min(hi[a], s + p["width"] - 1)
        else:
            raise ValueError(f"unknown predicate op {op!r}")
    return lo.astype(np.int16), hi.astype(np.int16)


@dataclasses.dataclass
class Requests(traffic.Requests):
    topics: np.ndarray  # [n] topic centre of each query
    lo: np.ndarray  # [n, M] int16 conjunctive bounds
    hi: np.ndarray  # [n, M] int16


class Deployment:
    def __init__(self, cfg: dict, seed: int):
        for key, value in IMPLEMENTED.items():
            if cfg.get(key) != value:
                raise ValueError(
                    f"deployment 'topics' runs {key}={value!r}, not "
                    f"{cfg.get(key)!r}")
        self.cfg = cfg
        self.corpus = Corpus(cfg, seed)
        self.n_rows = self.corpus.n_rows
        self._work = None

    def data(self):
        """Every row on the device with its list: (core [N, D] bf16,
        attrs [N, M] int16, list [N]).  Keeps on the host what the
        reference and the work count read."""
        self.centroids = self.corpus.centroids()
        core, attrs, lists = self.corpus.all_rows(self.centroids)
        self.lists, self.attrs = np.asarray(lists), np.asarray(attrs)
        self.counts = np.bincount(self.lists, minlength=self.cfg["n_lists"])
        return core, attrs, lists

    def build(self, variant: str = ""):
        """The data, then the program's RAM index and search function on
        it.  ``variant="sq8"`` serves the program's int8 lists instead: the
        control, one precision below the configuration's bf16."""
        from repro.core import HybridSpec
        from repro.core.ivf import build_from_assignments, quantize_index
        from repro.core.serving import make_fused_search_fn

        cfg = self.cfg
        core, attrs, lists = self.data()
        index, stats = build_from_assignments(
            HybridSpec(cfg["dim"], cfg["n_attrs"], metric=cfg["metric"]),
            self.centroids, core, attrs, lists,
            vpad=cfg.get("list_capacity"))
        del core, attrs, lists
        if variant == "sq8":
            index = quantize_index(index)
        elif variant:
            raise ValueError(f"unknown variant {variant!r}")
        jax.block_until_ready(index.vectors)
        if stats.n_dropped:
            raise RuntimeError(f"build dropped {stats.n_dropped} rows")
        fn = make_fused_search_fn(index, k=cfg["top_k"],
                                  n_probes=cfg["n_probes"],
                                  q_block=cfg["batch"])
        sizes = dict(rows=self.n_rows, dim=cfg["dim"],
                     n_attrs=cfg["n_attrs"], n_lists=cfg["n_lists"],
                     vpad=stats.vpad, max_list=stats.max_list_len,
                     mean_list=round(stats.mean_list_len, 1),
                     index_bytes=index.nbytes(), backend=fn.engine.backend,
                     store=str(index.vectors.dtype))
        return fn, dict(dim=cfg["dim"], n_attrs=cfg["n_attrs"], n_terms=1,
                        n_shards=1), sizes

    def free(self):
        """The RAM index lives in the search function alone."""

    def draw(self, mix: dict, n: int, rng, stream: int) -> Requests:
        """``n`` requests of the mix: classes in exact shares, seeded
        order; queries around the drawn topics from the seed's ``stream``."""
        card = self.cfg["attr_cardinality"]
        classes = mix["classes"]
        cls = traffic.classes(mix, n, rng)
        lo = np.empty((n, len(card)), np.int16)
        hi = np.empty((n, len(card)), np.int16)
        for i, c in enumerate(cls):
            lo[i], hi[i] = bounds(classes[c]["predicates"], card, rng)
        topics = traffic.topics(mix, n, self.cfg["n_topics"], rng)
        return Requests(self.corpus.queries(topics, stream), cls,
                        [c["name"] for c in classes], topics, lo, hi)

    def submit_args(self, r: Requests, i: int):
        return r.queries[i], (r.lo[i:i + 1], r.hi[i:i + 1])

    def batch(self, r: Requests, rows, size: int):
        """Queries [size, D] and their ``FilterSpec``, padded with zero rows
        as the server pads."""
        from repro.core.filters import FilterSpec

        b = len(rows)
        qq = np.zeros((size, self.cfg["dim"]), np.float32)
        ll = np.zeros((size, 1, self.cfg["n_attrs"]), np.int16)
        hh = np.zeros_like(ll)
        qq[:b], ll[:b, 0], hh[:b, 0] = r.queries[rows], r.lo[rows], r.hi[rows]
        return jnp.asarray(qq), FilterSpec(lo=jnp.asarray(ll),
                                           hi=jnp.asarray(hh))

    def probe_lists(self, queries: np.ndarray, t: int):
        probes, pv = reference.probe_lists(
            jnp.asarray(queries), self.centroids, jnp.asarray(self.counts),
            t=t)
        return np.asarray(probes), np.asarray(pv)

    def search(self, r: Requests, rows, probes: np.ndarray, k: int):
        return reference.search(self.corpus, self.lists, self.cfg["n_lists"],
                                r.queries[rows], r.lo[rows], r.hi[rows],
                                probes, k)

    def answer_faults(self, ids: np.ndarray, r: Requests, rows):
        return reference.answer_faults(ids, r.lo[rows], r.hi[rows],
                                       self.attrs, self.n_rows)

    def work(self, r: Requests, rows, size: int):
        cfg = self.cfg
        if self._work is None:
            self._work = ScanWork(
                self.lists, self.attrs, cfg["n_lists"], cfg["dim"],
                cfg["dim"] * jnp.dtype(cfg["store_dtype"]).itemsize)
        q = np.zeros((size, cfg["dim"]), np.float32)
        q[:len(rows)] = r.queries[rows]  # one program for every batch
        probes, _ = self.probe_lists(q, cfg["n_probes"])
        return self._work.batch(probes[:len(rows), :cfg["n_probes"]],
                                r.lo[rows], r.hi[rows], size)
