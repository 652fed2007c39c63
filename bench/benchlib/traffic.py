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
- ``classes``: filter classes, each with its ``name``, its ``share`` of the
  requests (exact counts, in seeded order) and its filter, in the
  vocabulary of the deployment that serves the mix
  (``bench/deployments/<name>.py``).

The deployment draws a mix's requests with ``classes`` and ``topics`` and
its own filters in between, and returns them as ``Requests``.
"""

from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass
class Requests:
    """What every deployment's requests carry; a deployment adds its own
    filter payload."""

    queries: np.ndarray  # [n, D] f32 query vectors
    cls: np.ndarray  # [n] index into class_names
    class_names: List[str]


def classes(mix: dict, n: int, rng) -> np.ndarray:
    """[n] class of each request: the mix's shares as exact counts, in
    seeded order."""
    counts = [int(round(c["share"] * n)) for c in mix["classes"]]
    counts[-1] = n - sum(counts[:-1])
    return rng.permutation(np.repeat(np.arange(len(counts)), counts))


def topic_weights(mix: dict, n_topics: int, rng) -> np.ndarray:
    t = mix["topics"]
    if t["dist"] == "uniform":
        return np.full(n_topics, 1.0 / n_topics)
    if t["dist"] == "zipf":
        w = 1.0 / np.arange(1, n_topics + 1) ** float(t["s"])
        w = w[np.argsort(rng.permutation(n_topics))]  # seeded rank order
        return w / w.sum()
    raise ValueError(f"unknown topic distribution {t['dist']!r}")


def topics(mix: dict, n: int, n_topics: int, rng) -> np.ndarray:
    """[n] topic centre of each request, under the mix's distribution."""
    return rng.choice(n_topics, size=n, p=topic_weights(mix, n_topics, rng))


def schedule(mix: dict, rate: float, seconds: float, rng) -> np.ndarray:
    """Send times in seconds from the window's start; the lead-in is
    negative.  Open loop: the times do not depend on the server."""
    if mix["arrivals"] != "poisson":
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    lead = float(mix["lead_in_s"])
    before = rng.uniform(-lead, 0.0, int(round(rate * lead)))
    inside = rng.uniform(0.0, seconds, int(round(rate * seconds)))
    return np.sort(np.concatenate([before, inside]))
