"""The one traffic generator: reads a mix file, makes a seeded schedule.

A mix file (``bench/traffic/<name>.json``) holds only parameters:

- ``arrivals``: ``"poisson"``.  A window of ``S`` seconds at rate ``R``
  holds exactly ``round(R·S)`` requests at uniform random times (a Poisson
  process given its count), and so does its lead-in, so every seed offers
  the same work.
- ``lead_in_s``: seconds of the same traffic sent before the window opens,
  so the server's queue is in its steady state when the window starts.
- ``topics``: ``{"dist": "uniform"}`` or ``{"dist": "zipf", "s": 1.0}``
  over the configuration's topic centres, in a rank order drawn from the
  seed.
- ``classes``: filter classes, each with its ``share`` of the requests
  (exact counts, in seeded order) and its ``predicates``, a conjunction of
  ``{"attr", "op", ...}`` with ``op`` one of ``eq`` (``value``, or a value
  drawn uniformly below the attribute's cardinality), ``le``, ``ge``, and
  ``window`` (``width`` consecutive values at a random start).
"""

from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

ATTR_MIN, ATTR_MAX = -32768, 32767  # the int16 attribute range


@dataclasses.dataclass
class Requests:
    topics: np.ndarray  # [n] topic centre of each query
    cls: np.ndarray  # [n] index into class_names
    lo: np.ndarray  # [n, M] int16 conjunctive bounds
    hi: np.ndarray  # [n, M] int16
    class_names: List[str]


def _bounds(preds, card, rng):
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


def topic_weights(mix: dict, n_topics: int, rng) -> np.ndarray:
    t = mix["topics"]
    if t["dist"] == "uniform":
        return np.full(n_topics, 1.0 / n_topics)
    if t["dist"] == "zipf":
        w = 1.0 / np.arange(1, n_topics + 1) ** float(t["s"])
        w = w[np.argsort(rng.permutation(n_topics))]  # seeded rank order
        return w / w.sum()
    raise ValueError(f"unknown topic distribution {t['dist']!r}")


def draw(mix: dict, n: int, card, n_topics: int, rng) -> Requests:
    """``n`` requests of the mix: classes in exact shares, seeded order."""
    classes = mix["classes"]
    counts = [int(round(c["share"] * n)) for c in classes]
    counts[-1] = n - sum(counts[:-1])
    cls = rng.permutation(np.repeat(np.arange(len(classes)), counts))
    lo = np.empty((n, len(card)), np.int16)
    hi = np.empty((n, len(card)), np.int16)
    for i, c in enumerate(cls):
        lo[i], hi[i] = _bounds(classes[c]["predicates"], card, rng)
    weights = topic_weights(mix, n_topics, rng)
    topics = rng.choice(n_topics, size=n, p=weights)
    return Requests(topics, cls, lo, hi, [c["name"] for c in classes])


def schedule(mix: dict, rate: float, seconds: float, rng) -> np.ndarray:
    """Send times in seconds from the window's start; the lead-in is
    negative.  Open loop: the times do not depend on the server."""
    if mix["arrivals"] != "poisson":
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    lead = float(mix["lead_in_s"])
    before = rng.uniform(-lead, 0.0, int(round(rate * lead)))
    inside = rng.uniform(0.0, seconds, int(round(rate * seconds)))
    return np.sort(np.concatenate([before, inside]))
