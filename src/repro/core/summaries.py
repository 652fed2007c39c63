"""Per-cluster attribute summaries: the filter-aware side of the planner.

The paper's core claim is that filtering belongs *inside* the index (§3.4,
§4.3), yet a geometry-only probe plan discovers post-hoc — after paying the
full HBM (RAM tier) or mmap-fetch (disk tier) cost — that a streamed cluster
contains zero rows passing the query's filter.  SIEVE's collection-of-indexes
and the attribute-filtering experimental study both observe that cheap
per-partition attribute metadata excludes most partitions under selective
filters.  This module is that metadata for the hybrid IVF index:

  * ``amin/amax [K, M] int16`` — closed per-cluster, per-attribute intervals
    covering every *live* row.  A DNF term whose interval is disjoint from a
    cluster's interval in ANY attribute cannot match any row of that cluster.
  * ``hist [K, M, B] int32`` — fixed-width per-attribute count histograms over
    the global attribute range (``edges_lo/edges_hi [M] int16``).  Two uses:
    a *sound* zero-mass refinement of the interval test (a term whose covered
    bins hold zero rows matches nothing, even inside the interval), and an
    expected-passing-count estimate that ranks surviving probes.

Both tests are conservative by construction: they may only *fail to prune*
(stale-wide intervals after tombstones, partial-bin overcounting), never
prune a cluster that still contains a passing row — so a pruned plan returns
bit-identical ids/scores to an unpruned one.  Maintenance mirrors that
contract: ``add`` widens intervals and adds histogram mass, ``tombstone``
leaves summaries stale (conservative), ``compact`` rebuilds the cluster's
row exactly (see ``core/update.py``).

Summaries are tiny — ``K·M·(2 + 4B)`` bytes plus edges — and always resident:
the disk tier counts them against ``resident_budget_bytes`` and consults them
*before* building the batch's fetch list, which is the whole point.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hybrid import ATTR_MAX, ATTR_MIN

Array = jax.Array

DEFAULT_N_BINS = 16


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ClusterSummaries:
    """Resident per-cluster attribute metadata (shapes above).

    An empty cluster carries the void interval ``[ATTR_MAX, ATTR_MIN]`` and
    zero histogram mass, so it can never match any term — consistent with
    ``counts == 0`` clusters being unprobeable in the centroid top-k.
    """

    amin: Array  # [K, M] int16
    amax: Array  # [K, M] int16
    hist: Array  # [K, M, B] int32 — live-row counts per fixed-width bin
    edges_lo: Array  # [M] int16 — global bin-range lower edge per attribute
    edges_hi: Array  # [M] int16 — global bin-range upper edge per attribute

    @property
    def n_clusters(self) -> int:
        return self.amin.shape[0]

    @property
    def n_attrs(self) -> int:
        return self.amin.shape[1]

    @property
    def n_bins(self) -> int:
        return self.hist.shape[-1]

    def nbytes(self) -> int:
        return sum(
            a.size * a.dtype.itemsize
            for a in (self.amin, self.amax, self.hist,
                      self.edges_lo, self.edges_hi)
        )


def attr_bins(attrs: Array, edges_lo: Array, edges_hi: Array,
              n_bins: int) -> Array:
    """Bin index of each attribute value, clipped into ``[0, n_bins)``.

    Values outside the global edge range land in the edge bins — sound for
    the zero-mass test (their mass is visible to any term reaching that edge
    bin, and irrelevant to terms that do not).
    """
    lo = edges_lo.astype(jnp.int32)
    span = jnp.maximum(edges_hi.astype(jnp.int32) - lo + 1, 1)
    b = ((attrs.astype(jnp.int32) - lo) * n_bins) // span
    return jnp.clip(b, 0, n_bins - 1)


def _hist_scatter(bins: Array, live: Array, n_bins: int) -> Array:
    """[..., M] bin indices + [...] live mask → [..., M, B] count histogram.

    Scatter-add over the input rows — peak memory is the *input* size, never
    the ``input × B`` one-hot a comparison-based reduction would build
    (ruinous at billion-row build time).
    """
    *lead, vpad, m = bins.shape
    flat_bins = bins.reshape(-1, vpad, m)
    flat_live = live.reshape(-1, vpad)
    r = flat_bins.shape[0]
    rows = jnp.broadcast_to(
        jnp.arange(r)[:, None, None], flat_bins.shape
    )
    cols = jnp.broadcast_to(
        jnp.arange(m)[None, None, :], flat_bins.shape
    )
    add = jnp.broadcast_to(
        flat_live[..., None].astype(jnp.int32), flat_bins.shape
    )
    hist = jnp.zeros((r, m, n_bins), jnp.int32).at[
        rows, cols, flat_bins
    ].add(add)
    return hist.reshape(*lead, m, n_bins)


@jax.jit
def _cluster_rows(attrs: Array, live: Array, edges_lo: Array,
                  edges_hi: Array, hist_width: Array
                  ) -> Tuple[Array, Array, Array]:
    """(amin, amax, hist) over the live rows of ``attrs [K, Vpad, M]``.

    ``hist_width`` is a zeros ``[B]`` template carrying the static bin count
    (jit re-specializes per width).
    """
    n_bins = hist_width.shape[0]
    a_hi = jnp.where(live[..., None], attrs, ATTR_MAX)
    a_lo = jnp.where(live[..., None], attrs, ATTR_MIN)
    amin = jnp.min(a_hi, axis=1).astype(jnp.int16)
    amax = jnp.max(a_lo, axis=1).astype(jnp.int16)
    bins = attr_bins(attrs, edges_lo, edges_hi, n_bins)  # [K, Vpad, M]
    hist = _hist_scatter(bins, live, n_bins)  # [K, M, B]
    return amin, amax, hist


def build_summaries(
    attrs: Array,
    ids: Array,
    *,
    n_bins: int = DEFAULT_N_BINS,
    edges: Optional[Tuple[Array, Array]] = None,
) -> ClusterSummaries:
    """Builds summaries from the index's flat lists (index-build time).

    Args:
      attrs: [K, Vpad, M] int16 attribute lists.
      ids:   [K, Vpad] int32 — rows with ``ids < 0`` (pads, tombstones) are
             excluded.
      n_bins: static histogram width B.
      edges: optional fixed ``(edges_lo, edges_hi)`` per-attribute bin range;
             default = the observed global min/max (so bins spend no width on
             values that never occur).  Pass the old edges when rebuilding a
             subset of clusters so histograms stay comparable.
    """
    live = ids >= 0  # [K, Vpad]
    if edges is None:
        any_live = jnp.any(live)
        a_hi = jnp.where(live[..., None], attrs, ATTR_MAX)
        a_lo = jnp.where(live[..., None], attrs, ATTR_MIN)
        edges_lo = jnp.where(
            any_live, jnp.min(a_hi, axis=(0, 1)), ATTR_MIN
        ).astype(jnp.int16)
        edges_hi = jnp.where(
            any_live, jnp.max(a_lo, axis=(0, 1)), ATTR_MAX
        ).astype(jnp.int16)
    else:
        edges_lo = jnp.asarray(edges[0], jnp.int16)
        edges_hi = jnp.asarray(edges[1], jnp.int16)
    amin, amax, hist = _cluster_rows(
        attrs, live, edges_lo, edges_hi, jnp.zeros((n_bins,), jnp.int32)
    )
    return ClusterSummaries(
        amin=amin, amax=amax, hist=hist, edges_lo=edges_lo, edges_hi=edges_hi
    )


def rebuild_cluster(summaries: ClusterSummaries, attrs_row: Array,
                    ids_row: Array, cluster) -> ClusterSummaries:
    """Recomputes one cluster's summary row exactly (compaction, rebuilds).

    Keeps the existing global edges so the refreshed histogram stays
    comparable with its neighbours.
    """
    live = ids_row >= 0  # [Vpad]
    a_hi = jnp.where(live[:, None], attrs_row, ATTR_MAX)
    a_lo = jnp.where(live[:, None], attrs_row, ATTR_MIN)
    amin = jnp.min(a_hi, axis=0).astype(jnp.int16)
    amax = jnp.max(a_lo, axis=0).astype(jnp.int16)
    bins = attr_bins(attrs_row, summaries.edges_lo, summaries.edges_hi,
                     summaries.n_bins)  # [Vpad, M]
    hist = _hist_scatter(bins[None], live[None], summaries.n_bins)[0]  # [M,B]
    return dataclasses.replace(
        summaries,
        amin=summaries.amin.at[cluster].set(amin),
        amax=summaries.amax.at[cluster].set(amax),
        hist=summaries.hist.at[cluster].set(hist),
    )


def widen_for_add(summaries: ClusterSummaries, assignments: Array,
                  attrs_new: Array, ok: Array) -> ClusterSummaries:
    """Folds a batch of appended rows into the summaries (``add_vectors``).

    Intervals widen via scatter-min/max and histogram mass is added at each
    row's bin; rows with ``ok == False`` (capacity drops) are excluded so the
    summaries keep describing exactly the rows the index holds.
    """
    b, m = attrs_new.shape
    a_hi = jnp.where(ok[:, None], attrs_new, ATTR_MAX).astype(jnp.int16)
    a_lo = jnp.where(ok[:, None], attrs_new, ATTR_MIN).astype(jnp.int16)
    amin = summaries.amin.at[assignments].min(a_hi, mode="drop")
    amax = summaries.amax.at[assignments].max(a_lo, mode="drop")
    bins = attr_bins(attrs_new, summaries.edges_lo, summaries.edges_hi,
                     summaries.n_bins)  # [B_rows, M]
    hist = summaries.hist.at[
        assignments[:, None], jnp.arange(m)[None, :], bins
    ].add(ok[:, None].astype(jnp.int32), mode="drop")
    return dataclasses.replace(summaries, amin=amin, amax=amax, hist=hist)


def pad_clusters(summaries: ClusterSummaries, k_new: int) -> ClusterSummaries:
    """Pads the cluster axis with void (never-matching) summary rows."""
    k, m = summaries.amin.shape
    if k_new < k:
        raise ValueError(f"cannot shrink K: {k} -> {k_new}")
    if k_new == k:
        return summaries
    dk = k_new - k
    return dataclasses.replace(
        summaries,
        amin=jnp.concatenate(
            [summaries.amin, jnp.full((dk, m), ATTR_MAX, jnp.int16)], 0
        ),
        amax=jnp.concatenate(
            [summaries.amax, jnp.full((dk, m), ATTR_MIN, jnp.int16)], 0
        ),
        hist=jnp.concatenate(
            [summaries.hist,
             jnp.zeros((dk, m, summaries.n_bins), jnp.int32)], 0
        ),
    )


# ---------------------------------------------------------------------------
# Per-cluster geometric score bounds (bound-driven early termination)
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ClusterBounds:
    """Resident per-cluster geometric statistics for per-probe score bounds.

    ``radius[c]`` is the max distance from cluster ``c``'s centroid to any
    live *stored* row (SQ8 rows measured after dequantization — the scan
    scores the stored representation, so the bound must cover it, not the
    original floats).  ``slack[c]`` is the max of ``‖x̂‖² − norms_row`` over
    live rows: the l2 kernel scores ``2q·x̂ − norms_row``, and the geometric
    bound on ``2q·x̂ − ‖x̂‖²`` converts to the kernel's score space by adding
    this slack.  Both are conservative the same way the attribute summaries
    are: tombstones leave them stale-wide (a sound over-estimate), a
    compaction rebuilds the row exactly, and an empty cluster carries
    ``radius == slack == 0`` (vacuous — the probe is unprobeable anyway).

    Tiny (``8·K`` bytes) and always resident, like the summaries: the
    terminated executor consults them per batch before any flat list is
    scanned.
    """

    radius: Array  # [K] f32 — max ‖x̂ − c‖ over live stored rows
    slack: Array   # [K] f32 — max (‖x̂‖² − norms_row) over live rows (l2)

    @property
    def n_clusters(self) -> int:
        return self.radius.shape[0]

    def nbytes(self) -> int:
        return sum(
            a.size * a.dtype.itemsize for a in (self.radius, self.slack)
        )


@jax.jit
def _bounds_rows(vectors: Array, scales: Optional[Array], live: Array,
                 centroids: Array,
                 norms: Optional[Array]) -> Tuple[Array, Array]:
    """(radius, slack) over the live rows of ``vectors [K, Vpad, D]``.

    The rows are widened to f32 inside the jit, so the widening fuses into
    the reductions instead of materializing an f32 copy of the lists.
    """
    x32 = _stored_f32(vectors, scales)
    diff = x32 - centroids.astype(jnp.float32)[:, None, :]
    d2 = jnp.sum(diff * diff, axis=-1)  # [K, Vpad]
    # d2 >= 0, so masking dead rows to 0 keeps the max sound and gives an
    # empty cluster radius 0 without a separate any-live branch
    radius = jnp.sqrt(jnp.max(jnp.where(live, d2, 0.0), axis=1))
    if norms is None:
        slack = jnp.zeros_like(radius)
    else:
        sl = jnp.sum(x32 * x32, axis=-1) - norms.astype(jnp.float32)
        any_live = jnp.any(live, axis=1)
        slack = jnp.where(
            any_live, jnp.max(jnp.where(live, sl, -jnp.inf), axis=1), 0.0
        )
    return radius, slack


def _stored_f32(vectors: Array, scales: Optional[Array]) -> Array:
    """The rows as the kernel scores them: dequantized SQ8 / f32-cast."""
    x32 = jnp.asarray(vectors).astype(jnp.float32)
    if scales is not None:
        x32 = x32 * jnp.asarray(scales, jnp.float32)[..., None]
    return x32


def build_bounds(centroids: Array, vectors: Array, ids: Array,
                 norms: Optional[Array] = None,
                 scales: Optional[Array] = None) -> ClusterBounds:
    """Builds the per-cluster score-bound statistics from the flat lists.

    Args mirror the index's resident arrays: ``vectors [K, Vpad, D]`` (store
    dtype; int8 codes with ``scales`` under SQ8), ``ids [K, Vpad]`` (rows
    with ``ids < 0`` excluded), ``norms [K, Vpad]`` for l2.
    """
    live = jnp.asarray(ids) >= 0
    radius, slack = _bounds_rows(
        jnp.asarray(vectors), None if scales is None else jnp.asarray(scales),
        live, jnp.asarray(centroids),
        None if norms is None else jnp.asarray(norms),
    )
    return ClusterBounds(radius=radius, slack=slack)


def rebuild_cluster_bounds(bounds: ClusterBounds, centroid_row: Array,
                           vectors_row: Array, ids_row: Array,
                           norms_row: Optional[Array],
                           scales_row: Optional[Array],
                           cluster) -> ClusterBounds:
    """Recomputes one cluster's bound row exactly (compaction, rebuilds)."""
    radius, slack = _bounds_rows(
        jnp.asarray(vectors_row)[None],
        None if scales_row is None else jnp.asarray(scales_row)[None],
        (jnp.asarray(ids_row) >= 0)[None],
        jnp.asarray(centroid_row)[None],
        None if norms_row is None else jnp.asarray(norms_row)[None],
    )
    return dataclasses.replace(
        bounds,
        radius=bounds.radius.at[cluster].set(radius[0]),
        slack=bounds.slack.at[cluster].set(slack[0]),
    )


def can_match(summaries: ClusterSummaries, lo: Array, hi: Array) -> Array:
    """[Q, K] bool — can any live row of cluster k pass query q's filter?

    Branch-free and jit-friendly (the planner calls it inside its jitted plan
    stage).  A cluster "can match" iff SOME DNF term overlaps its summary in
    EVERY attribute, where per-attribute overlap requires both

      * interval intersection: ``max(term_lo, amin) <= min(term_hi, amax)``
        (this form is void-term safe — a voided term's ``lo > hi`` can never
        intersect anything), and
      * nonzero histogram mass over the term's covered bins — a sound
        refinement: partial bins overcount, so zero mass proves zero rows.

    False guarantees zero passing rows (prunable); True guarantees nothing.
    """
    amin = summaries.amin.astype(jnp.int32)[None, None]  # [1, 1, K, M]
    amax = summaries.amax.astype(jnp.int32)[None, None]
    tlo = lo.astype(jnp.int32)[:, :, None, :]  # [Q, F, 1, M]
    thi = hi.astype(jnp.int32)[:, :, None, :]
    overlap = jnp.maximum(tlo, amin) <= jnp.minimum(thi, amax)  # [Q, F, K, M]

    n_bins = summaries.n_bins
    # cumulative mass per cluster/attr: cdf[..., b] = rows in bins < b
    cdf = jnp.concatenate(
        [jnp.zeros_like(summaries.hist[..., :1]),
         jnp.cumsum(summaries.hist, axis=-1)], axis=-1
    )  # [K, M, B+1]
    blo = attr_bins(lo, summaries.edges_lo, summaries.edges_hi, n_bins)
    bhi = attr_bins(hi, summaries.edges_lo, summaries.edges_hi, n_bins)
    # mass of bins blo..bhi inclusive = cdf[bhi+1] - cdf[blo], gathered per
    # (cluster, attr) at each term's bin bounds: [Q, F, K, M]
    hi_mass = jnp.take_along_axis(
        cdf[None, None], (bhi + 1)[:, :, None, :, None], axis=-1
    )[..., 0]
    lo_mass = jnp.take_along_axis(
        cdf[None, None], blo[:, :, None, :, None], axis=-1
    )[..., 0]
    nonzero = (hi_mass - lo_mass) > 0
    per_term = jnp.all(jnp.logical_and(overlap, nonzero), axis=-1)  # [Q,F,K]
    return jnp.any(per_term, axis=1)  # [Q, K]


def expected_passing(summaries: ClusterSummaries, lo: Array, hi: Array,
                     counts: Array) -> Array:
    """[Q, K] f32 — histogram-mass estimate of rows passing each filter.

    Per term and attribute, the covered-bin mass (partial bins included, so
    this over-estimates) is turned into a passing fraction; attributes are
    combined under independence and terms are summed (clipped to the live
    count).  Only a *ranking* signal — pruning soundness never rides on it.
    """
    n_bins = summaries.n_bins
    cdf = jnp.concatenate(
        [jnp.zeros_like(summaries.hist[..., :1]),
         jnp.cumsum(summaries.hist, axis=-1)], axis=-1
    )
    total = jnp.maximum(cdf[..., -1], 1)  # [K, M] live rows (per-attr alias)
    blo = attr_bins(lo, summaries.edges_lo, summaries.edges_hi, n_bins)
    bhi = attr_bins(hi, summaries.edges_lo, summaries.edges_hi, n_bins)
    hi_mass = jnp.take_along_axis(
        cdf[None, None], (bhi + 1)[:, :, None, :, None], axis=-1
    )[..., 0]
    lo_mass = jnp.take_along_axis(
        cdf[None, None], blo[:, :, None, :, None], axis=-1
    )[..., 0]
    frac = (hi_mass - lo_mass).astype(jnp.float32) / total[None, None]
    void = (lo > hi).any(axis=-1)  # [Q, F] — voided spare terms pass nothing
    per_term = jnp.where(
        void[:, :, None], 0.0, jnp.prod(frac, axis=-1)
    )  # [Q, F, K]
    est = jnp.sum(per_term, axis=1) * counts[None, :].astype(jnp.float32)
    return jnp.minimum(est, counts[None, :].astype(jnp.float32))
