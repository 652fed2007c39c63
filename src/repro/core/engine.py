"""Pipelined search execution engine: plan → fetch → scan → merge.

The fused search path used to be a monolith (``search_fused_tiled`` ran the
jitted plan, a synchronous whole-batch gather, and one jitted scan/merge
back-to-back).  That serializes disk IO behind device compute — the disk
tier's dominant cost — and provisions every batch's slot tables for the
unpruned worst case.  This module decomposes the path into explicit stages
owned by :class:`SearchEngine`:

    plan   — jitted, resident-state only (:func:`plan_fused_tiled`): centroid
             top-k, filter-aware probe pruning, per-tile probe dedup.  Emits a
             :class:`SearchPlan` carrying per-tile slot tables and first-need
             fetch lists (:class:`TileWork`).
    fetch  — materialize the slots' cluster operands through the pluggable
             :class:`repro.core.blockstore.BlockStore` protocol.  RAM tier:
             the resident ``[K, Vpad, ...]`` arrays (a no-op).  Disk tier: a
             ``LocalBlockStore`` pages the plan's fetch list through the
             cluster cache; a ``ShardedBlockStore`` routes it over a
             consistent-hash ring of peer caches.  Pipelined fetches ride
             the store's ``submit``/``wait`` pair, and a per-batch *operand
             cache* pulls each cluster block through the store once per
             batch, reusing it across every tile of the batch that probes
             the cluster.
    scan   — jitted (:func:`_scan_merge_tiled`): the tiled Pallas/XLA kernel
             over the slot tables, one ``[QB, D] @ [D, VB]`` matmul per
             streamed block, per-probe ``[QB, k]`` fragments.
    merge  — jitted, fused into the scan call: monoid top-k across each
             query's probes, l2 constant fix-up, scan accounting.

Two executors share those stages and return bit-identical results:

  * **sync** (``pipeline="off"``) — the original monolith: one fetch for the
    whole batch, one scan over all ``n_tiles · u_cap`` slots.
  * **pipelined** (``pipeline="on"``) — double-buffered: while tile *i*
    scans on device, a background worker gathers tile *i+1*'s clusters from
    disk (``pipeline_depth`` tiles stay in flight).  Per-tile scans reuse one
    compiled shape, so the pipeline adds no recompiles.

On top of the same plan objects the engine provisions ``u_cap`` adaptively
(``adaptive_u_cap``): the plan runs at the always-sufficient worst-case
table width, the observed post-prune per-tile unique-cluster counts are
bucketed into a fixed power-of-two set of compiled scan shapes
(:func:`u_cap_buckets`), and the slot tables are shrunk host-side to the
smallest sufficient bucket — selective filters scan (and the disk tier
gathers) small slot tables instead of the unpruned worst case, with at most
``len(buckets)`` scan compilations ever (see :func:`scan_compile_count`).
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import blockstore as blockstore_lib
from repro.core import probes as probes_lib
from repro.core import summaries as summaries_lib
from repro.core import topk as topk_lib
from repro.core.filters import FilterSpec
from repro.core.ivf import round_up
from repro.core.obs import (StageHistogram, _flatten_metrics,
                            render_prometheus, render_stage_histograms,
                            span)
from repro.core.search import SearchResult, centroid_scores

Array = jax.Array


# ---------------------------------------------------------------------------
# Stage primitives (jitted).  These are module-level so their jit caches are
# shared by every SearchEngine in the process.
# ---------------------------------------------------------------------------


def tiled_scan_xla(
    slot_cluster, slot_tile, queries, lo, hi, vectors, attrs, ids,
    norms, scales, *, metric: str, k: int, q_block: int, chunk: int = 8,
):
    """XLA streaming executor with the tiled kernel's exact contract.

    Chunked ``lax.map`` over slots: each step gathers ``chunk`` cluster
    blocks, scores them against their query tiles and immediately reduces to
    ``[QB, k]`` — the full per-slot score matrix never exists, matching the
    kernel's memory bound.  This is the fast CPU path (Mosaic needs a real
    TPU to lower non-interpreted).
    """
    d = queries.shape[-1]
    qt = queries.reshape(-1, q_block, d).astype(jnp.float32)
    lot = lo.reshape(-1, q_block, *lo.shape[1:]).astype(jnp.int32)
    hit = hi.reshape(-1, q_block, *hi.shape[1:]).astype(jnp.int32)

    def one(args):
        sc, st = args
        v = jnp.take(vectors, sc, axis=0).astype(jnp.float32)  # [Vpad, D]
        qb = jnp.take(qt, st, axis=0)  # [QB, D]
        scores = qb @ v.T  # [QB, Vpad]
        if scales is not None:
            scores = scores * jnp.take(scales, sc, axis=0)[None, :]
        if metric == "l2":
            scores = 2.0 * scores - jnp.take(norms, sc, axis=0)[None, :]
        a = jnp.take(attrs, sc, axis=0).astype(jnp.int32)  # [Vpad, M]
        qlo = jnp.take(lot, st, axis=0)  # [QB, F, M]
        qhi = jnp.take(hit, st, axis=0)
        inside = jnp.logical_and(
            a[None, :, None, :] >= qlo[:, None],
            a[None, :, None, :] <= qhi[:, None],
        )  # [QB, Vpad, F, M]
        fmask = jnp.any(jnp.all(inside, -1), -1)
        live = jnp.take(ids, sc, axis=0) >= 0
        mask = jnp.logical_and(fmask, live[None, :])
        svals, sids = topk_lib.masked_topk(
            scores, mask, k,
            ids=jnp.broadcast_to(jnp.take(ids, sc, axis=0), scores.shape),
        )
        return svals, sids, jnp.sum(mask.astype(jnp.int32), axis=-1)

    return jax.lax.map(
        one, (slot_cluster, slot_tile), batch_size=min(chunk, slot_cluster.shape[0])
    )


@functools.partial(
    jax.jit,
    static_argnames=("metric", "n_probes", "q_block", "u_cap", "cast_dtype",
                     "t_max"),
)
@jax.named_scope("plan")
def plan_fused_tiled(
    centroids: Array,
    counts: Array,
    queries: Array,
    lo: Array,
    hi: Array,
    *,
    metric: str,
    n_probes: int,
    q_block: int,
    u_cap: int,
    cast_dtype,
    summaries=None,
    t_max: Optional[int] = None,
    route_entry=None,
    members=None,
):
    """Plan stage: centroid probe + per-tile dedup over resident state.

    Runs entirely on the *resident* state (centroids + counts + attribute
    summaries), so the disk tier can plan — and hand ``slot_cluster`` to its
    cluster cache as the batch's fetch list — before any flat list is paged
    in.  Returns ``(slot_cluster, slot_tile, slot_of_probe, probe_ok,
    n_unique, queries_pad, lo_pad, hi_pad, n_pruned, geo_probes,
    geo_valid)``; queries/bounds come back padded to whole ``q_block`` tiles
    with edge rows (whose probes dedupe into the last real query's slots, so
    padding adds no scan work).  ``geo_probes``/``geo_valid`` are each
    query's *geometric* top-``n_probes`` candidate clusters (pre-widening,
    pre-pruning): the delta tier masks its RAM rows with exactly this set so
    a delta row only competes for queries whose probe budget would have
    reached its cluster — the condition for bit-parity with a from-scratch
    rebuild at the same logical state.

    With ``summaries`` (a :class:`repro.core.summaries.ClusterSummaries`),
    the plan is filter-aware: a branch-free disjointness test between each
    query's DNF terms and the per-cluster interval/histogram summaries marks
    clusters the filter provably cannot match, and those probes are dropped
    *before* the per-tile dedup — they never get a slot, are never fetched
    by ``probes.fetch_order``, and are never scanned.  Results stay
    bit-identical to the unpruned plan (only zero-passing-row clusters can
    be pruned).

    ``t_max`` (static, > n_probes) additionally enables adaptive probe
    widening (paper §4.3 selectivity-adaptive T): each query's probe set is
    refilled with its next-best *unpruned* centroids from the geometric
    top-``t_max``, so selective filters keep ``n_probes`` productive probes
    instead of silently scanning fewer clusters.  Unfiltered queries prune
    nothing, refill nothing, and plan exactly as before.  Within the refill
    ranking, the summaries' histogram-mass estimate of each cluster's
    expected passing count breaks exact centroid-score ties.

    ``route_entry`` ([Q] int32, −1 = flat) + ``members`` ([E, K_base] int32,
    −1 = scan parent) remap routed queries' probes from base cluster ids to
    the chosen catalog entry's sub-partition ids *after* the centroid top-k
    (probing geometry stays base-only — sub centroids are never scored) and
    *before* the per-tile dedup, so sub ids flow into the slot tables, fetch
    lists and every (cluster_id, gen)-keyed cache below.  ``geo_probes``
    stays base-id (the delta tier's membership mask is defined over base
    assignments).  Subsumption (checked host-side by the catalog's router)
    guarantees the remapped scan is bit-identical to the flat one.
    """
    scores = centroid_scores(centroids, counts, queries, metric=metric)
    q = queries.shape[0]
    if summaries is None:
        cvals, probe_ids = jax.lax.top_k(scores, n_probes)
        probe_ids = probe_ids.astype(jnp.int32)  # [Q, T]
        geo_ids = probe_ids
        geo_ok = cvals > topk_lib.NEG_INF / 2
        probe_valid = None
        n_pruned = jnp.zeros((q,), jnp.int32)
    else:
        cm = summaries_lib.can_match(summaries, lo, hi)  # [Q, K]
        width = n_probes if t_max is None else t_max
        cvals, cand = jax.lax.top_k(scores, width)  # [Q, W] geometric order
        cm_c = jnp.take_along_axis(cm, cand, axis=1)  # [Q, W]
        real = cvals > topk_lib.NEG_INF / 2  # exclude empty/padded clusters
        # geometric top-n_probes, captured before widening re-ranks cand:
        # the delta tier's membership mask must see the same probe set a
        # rebuilt index's planner would produce.
        geo_ids = cand[:, :n_probes].astype(jnp.int32)
        geo_ok = real[:, :n_probes]
        # accounting: probes a geometry-only planner would have scanned (and
        # the disk tier fetched) that the filter proved empty
        n_pruned = jnp.sum(
            jnp.logical_and(~cm_c[:, :n_probes], real[:, :n_probes])
            .astype(jnp.int32), axis=-1,
        )
        if t_max is None:
            # exact mode: the geometric top-T minus its pruned members
            probe_ids = cand.astype(jnp.int32)
            probe_valid = jnp.logical_and(cm_c, real)
        else:
            # widened mode: re-rank candidates by (centroid score, expected
            # passing mass) — the histogram estimate only breaks exact score
            # ties — then keep each query's first n_probes unpruned ones.
            epass = summaries_lib.expected_passing(summaries, lo, hi, counts)
            ep_c = jnp.take_along_axis(epass, cand, axis=1)
            order = jnp.lexsort((-ep_c, -cvals), axis=-1)  # last key primary
            cand = jnp.take_along_axis(cand, order, axis=1)
            cm_c = jnp.take_along_axis(cm_c, order, axis=1)
            real = jnp.take_along_axis(real, order, axis=1)
            ok = jnp.logical_and(cm_c, real)
            rank = jnp.cumsum(ok.astype(jnp.int32), axis=1) - 1
            probe_ids = cand.astype(jnp.int32)
            probe_valid = jnp.logical_and(ok, rank < n_probes)
    if members is not None:
        # partition remap: routed queries swap each probed base cluster for
        # the entry's sub-partition of it (-1 member = keep the parent)
        ent = jnp.maximum(route_entry, 0)
        sub = members[ent[:, None], probe_ids]  # [Q, W]
        probe_ids = jnp.where(
            jnp.logical_and(route_entry[:, None] >= 0, sub >= 0),
            sub, probe_ids,
        )
    probe_pad = probes_lib.pad_to_tiles(probe_ids, q_block)  # [Qpad, W]
    valid_pad = (
        None if probe_valid is None
        else probes_lib.pad_to_tiles(probe_valid, q_block)
    )
    geo_pad = probes_lib.pad_to_tiles(geo_ids, q_block)  # [Qpad, T]
    geo_ok_pad = probes_lib.pad_to_tiles(geo_ok, q_block)
    queries_pad = probes_lib.pad_to_tiles(queries.astype(cast_dtype), q_block)
    lo_pad = probes_lib.pad_to_tiles(lo, q_block)
    hi_pad = probes_lib.pad_to_tiles(hi, q_block)
    slot_cluster, slot_tile, slot_of_probe, probe_ok, n_unique = (
        probes_lib.plan_probe_tiles(probe_pad, q_block=q_block, u_cap=u_cap,
                                    probe_valid=valid_pad)
    )
    return (slot_cluster, slot_tile, slot_of_probe, probe_ok, n_unique,
            queries_pad, lo_pad, hi_pad, n_pruned, geo_pad, geo_ok_pad)


@functools.partial(
    jax.jit,
    static_argnames=("metric", "k", "q", "q_block", "v_block", "backend"),
)
def _scan_merge_tiled(
    slot_cluster: Array,
    slot_tile: Array,
    slot_of_probe: Array,
    probe_ok: Array,
    queries: Array,      # [Q, D] original (for the l2 ‖q‖² constant)
    queries_pad: Array,  # [Qpad, D] cast + tile-padded
    lo_pad: Array,
    hi_pad: Array,
    vectors: Array,
    attrs: Array,
    ids: Array,
    norms: Optional[Array],
    scales: Optional[Array],
    *,
    metric: str,
    k: int,
    q: int,
    q_block: int,
    v_block: int,
    backend: str,
) -> Tuple[SearchResult, Optional[Array]]:
    """Scan + merge stages: scan the planned slots, merge per-probe fragments.

    ``vectors/attrs/ids/...`` are indexed by ``slot_cluster`` rows — either
    the full ``[K, Vpad, ...]`` resident arrays (RAM tier) or batch-local
    gathered ``[S, Vpad, ...]`` blocks with slot-local ids (disk tier).  The
    kernel only ever dereferences rows named in ``slot_cluster``, so the two
    are indistinguishable to it.  The pipelined executor calls this once per
    tile (``q = q_block``, ``slot_tile ≡ 0``) with identical per-slot
    arithmetic, so its results are bit-identical to one whole-batch call.

    Returns the result and the kernel's top-k insertion passes summed over
    the slots (a device scalar; None on the ``xla`` executor, which has no
    such fold).
    """
    qpad = queries_pad.shape[0]
    svals, sids, snpass, fold = _scan_slots(
        slot_cluster, queries_pad, lo_pad, hi_pad, vectors, attrs, ids,
        norms, scales, slot_tile, metric=metric, k=k, q_block=q_block,
        v_block=v_block, backend=backend,
    )

    with jax.named_scope("merge"):
        # Per-probe candidate fragments, then the monoid merge across T
        # probes.  Probes that overflowed an undersized u_cap are dropped
        # soundly (their fragments masked out), mirroring the distributed
        # dispatch's P_cap.
        row = jnp.arange(qpad, dtype=jnp.int32) % q_block  # [Qpad]
        vals_qt = svals[slot_of_probe, row[:, None]]  # [Qpad, T, k]
        ids_qt = sids[slot_of_probe, row[:, None]]
        npass_qt = snpass[slot_of_probe, row[:, None]]  # [Qpad, T]
        vals_qt = jnp.where(probe_ok[..., None], vals_qt, topk_lib.NEG_INF)
        ids_qt = jnp.where(probe_ok[..., None], ids_qt, -1)
        npass_qt = jnp.where(probe_ok, npass_qt, 0)
        vals, out_ids = topk_lib.merge_topk_many(vals_qt, ids_qt, k, axis=1)
        vals, out_ids = vals[:q], out_ids[:q]

        if metric == "l2":
            q2 = jnp.sum(queries.astype(jnp.float32) ** 2, -1)  # [Q]
            vals = jnp.where(
                vals > topk_lib.NEG_INF / 2, vals - q2[:, None], vals
            )

        n_passed = jnp.sum(npass_qt[:q], axis=-1)
        # Scan accounting through the slot tables: a probe's slot scans
        # exactly its cluster, so live-rows-per-slot gathered by
        # slot_of_probe equals the old per-cluster lookup — and works when
        # only gathered rows exist.
        # [K or S]
        live_per_row = jnp.sum((ids >= 0).astype(jnp.int32), axis=-1)
        live_per_slot = jnp.take(live_per_row, slot_cluster)  # [S_flat]
        n_scanned = jnp.sum(
            jnp.take(live_per_slot, slot_of_probe[:q])
            * probe_ok[:q].astype(jnp.int32),
            axis=-1,
        )
        return SearchResult(vals, out_ids, n_scanned, n_passed), fold


@functools.partial(
    jax.jit,
    static_argnames=("metric", "k", "q_block", "v_block", "backend"),
)
@jax.named_scope("scan")
def _scan_slots(
    slot_cluster: Array,   # [S] rows into the operand arrays
    queries_pad: Array,    # [Qpad, D] cast + tile-padded queries
    lo_pad: Array,
    hi_pad: Array,
    vectors: Array,
    attrs: Array,
    ids: Array,
    norms: Optional[Array],
    scales: Optional[Array],
    slot_tile: Optional[Array] = None,  # [S] tile of each slot; None ⇒ 0
    *,
    metric: str,
    k: int,
    q_block: int,
    v_block: int,
    backend: str,
):
    """Scan stage alone: the slots' ``[S, QB, k]`` fragments, their
    ``[S, QB]`` pass counts, and the kernel's top-k insertion passes summed
    over the slots (None on the ``xla`` executor).

    :func:`_scan_merge_tiled`'s scan half; the bound-driven executor calls
    it on a slice of one tile's slot table.  Per-slot arithmetic is
    independent of which other slots share the call, so fragments from
    segmented scans are bitwise the fragments one whole-table scan produces
    — the bound-driven executor's exactness rides on that.
    """
    from repro.kernels.filtered_scan.filtered_scan import filtered_scan_tiled

    if slot_tile is None:
        slot_tile = jnp.zeros((slot_cluster.shape[0],), jnp.int32)
    if backend in ("pallas", "pallas_interpret"):
        svals, sids, snpass, nfold = filtered_scan_tiled(
            slot_cluster, slot_tile, queries_pad, lo_pad, hi_pad,
            vectors, attrs, ids, norms, scales,
            metric=metric, k=k, q_block=q_block, v_block=v_block,
            interpret=backend == "pallas_interpret",
        )
        return svals, sids, snpass, jnp.sum(nfold)
    if backend == "xla":
        return tiled_scan_xla(
            slot_cluster, slot_tile, queries_pad, lo_pad, hi_pad,
            vectors, attrs, ids, norms, scales,
            metric=metric, k=k, q_block=q_block,
        ) + (None,)
    raise ValueError(backend)


@functools.partial(jax.jit, static_argnames=("metric", "k", "q"))
@jax.named_scope("merge")
def _merge_tile_fragments(
    svals: Array,          # [S_pad, QB, k] per-slot fragments (filler where
    sids: Array,           #   a segment was never scanned)
    snpass: Array,         # [S_pad, QB]
    slot_of_probe: Array,  # [QB, W] tile-local slot pointers
    pair_ok: Array,        # [QB, W] — probe contributes candidates
    scan_ok: Array,        # [QB, W] — probe's slot was actually scanned
    queries: Array,        # [QB, D] original dtype (l2 ‖q‖² constant)
    live_per_slot: Array,  # [S_pad] live rows of each slot's cluster
    *,
    metric: str,
    k: int,
    q: int,
) -> SearchResult:
    """Merge stage for a bound-terminated tile.

    :func:`_scan_merge_tiled`'s merge half with two masks instead of one:
    ``pair_ok`` additionally excludes ε-dropped (query, slot) pairs — their
    fragments may exist (another query kept the segment alive) but the
    bounded-mode contract is that the result equals an exact top-k over the
    surviving probe universe, so they must not leak in.  Provably-dropped
    pairs whose segment was scanned anyway stay *included*: every candidate
    they hold is strictly below the query's final kth, so including them is
    what keeps ``termination="exact"`` bitwise identical to the untruncated
    merge.  ``scan_ok`` keeps ``n_scanned`` honest (terminated slots did no
    scan work).
    """
    row = jnp.arange(svals.shape[1], dtype=jnp.int32)  # [QB]
    vals_qt = svals[slot_of_probe, row[:, None]]  # [QB, W, k]
    ids_qt = sids[slot_of_probe, row[:, None]]
    npass_qt = snpass[slot_of_probe, row[:, None]]  # [QB, W]
    vals_qt = jnp.where(pair_ok[..., None], vals_qt, topk_lib.NEG_INF)
    ids_qt = jnp.where(pair_ok[..., None], ids_qt, -1)
    npass_qt = jnp.where(pair_ok, npass_qt, 0)
    vals, out_ids = topk_lib.merge_topk_many(vals_qt, ids_qt, k, axis=1)
    vals, out_ids = vals[:q], out_ids[:q]

    if metric == "l2":
        q2 = jnp.sum(queries.astype(jnp.float32) ** 2, -1)
        vals = jnp.where(
            vals > topk_lib.NEG_INF / 2, vals - q2[:q, None], vals
        )

    n_passed = jnp.sum(npass_qt[:q], axis=-1)
    n_scanned = jnp.sum(
        jnp.take(live_per_slot, slot_of_probe[:q])
        * scan_ok[:q].astype(jnp.int32),
        axis=-1,
    )
    return SearchResult(vals, out_ids, n_scanned, n_passed)


def resolve_prune(index, prune: str):
    """Resolves the ``prune`` knob against an index's summaries.

    Returns the :class:`~repro.core.summaries.ClusterSummaries` to plan with,
    or None for no pruning.  ``"auto"`` prunes iff the index carries
    summaries; ``"on"`` demands them; ``"off"`` never prunes.
    """
    summ = getattr(index, "summaries", None)
    if prune == "off":
        return None
    if prune == "on":
        if summ is None:
            raise ValueError(
                "prune='on' but the index has no cluster summaries — build "
                "with with_summaries=True or re-save the checkpoint (layout "
                "v2.1), or use prune='auto'"
            )
        return summ
    if prune == "auto":
        return summ
    raise ValueError(f"prune must be 'auto'|'on'|'off', got {prune!r}")


@jax.jit
def _batch_pass_fraction(summaries, counts, lo, hi):
    """Per-query expected passing-mass fraction from the resident summaries
    — the cheap, tier-agnostic selectivity estimate (the disk tier has no
    resident attrs to sample)."""
    ep = summaries_lib.expected_passing(summaries, lo, hi, counts)  # [Q, K]
    tot = jnp.maximum(jnp.sum(counts.astype(jnp.float32)), 1.0)
    return jnp.sum(ep, axis=1) / tot


# t_max="auto" widening factors: powers of two over n_probes, so the set of
# distinct plan/scan widths a serving mix can trigger stays bounded (same
# bounded-compile argument as the u_cap buckets).
AUTO_T_FACTORS = (2, 4, 8)


def resolve_auto_t_max(summaries, counts, lo, hi, n_probes: int,
                       n_clusters: int,
                       factors: Tuple[int, ...] = AUTO_T_FACTORS
                       ) -> Optional[int]:
    """Summary-driven per-batch probe widening (``t_max="auto"``).

    Estimates the batch's filter selectivity from the summaries' expected
    passing mass and widens the probe search proportionally: a batch whose
    filters pass ~1/f of the corpus gets its pruned probes refilled from the
    geometric top-``f·n_probes`` (capped at ``factors[-1]``, bucketed into
    powers of two so compiles stay bounded).  Unfiltered batches estimate
    selectivity ~1 and return None — bit-identical to the static plan.
    """
    if summaries is None:
        return None
    sel = float(np.median(np.asarray(
        _batch_pass_fraction(summaries, counts, lo, hi)
    )))
    need = 1.0 / max(sel, 1e-9)
    factor = 1
    for f in factors:
        if need >= f:
            factor = f
    if factor == 1:
        return None
    return min(factor * n_probes, n_clusters)


# ---------------------------------------------------------------------------
# Plan objects
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TileWork:
    """One query tile's slice of a :class:`SearchPlan` (host-side).

    ``fetch`` is the tile's *novel* cluster list — ids not needed by any
    earlier tile, in first-need (slot) order; concatenating every tile's
    ``fetch`` reproduces ``probes.fetch_order`` for the whole plan, which is
    what a slot-granular pager (or a multi-host cache router) consumes.
    ``release`` is the mirror image — clusters no *later* tile needs — and
    is what lets the per-batch operand cache free each record right after
    its last consumer, keeping reuse inside the disk tier's memory budget.
    """

    tile: int
    slot_cluster: np.ndarray  # [u_cap] int32 — global cluster per slot
    n_unique: int             # live slots (the rest are pads)
    fetch: np.ndarray         # novel clusters, first-need order
    release: np.ndarray       # clusters whose last need is this tile


@dataclasses.dataclass
class TermState:
    """Per-batch bound-driven termination state (host-side numpy).

    Built by :meth:`SearchEngine._prepare_termination` *after* the slot
    tables have been permuted best-bound-first, so every array here indexes
    ``(tile, query-row, slot-position)`` in the order the segmented executor
    scans.  ``ub`` already carries the dtype-aware rounding margin — the
    executor compares it raw against the running kth.
    """

    epsilon: float        # ε-drop threshold (0 in termination="exact")
    seg: int              # slot positions per segment (multiple of 4)
    n_seg: int            # segments per tile
    cap: int              # true table width (cap_pad = seg · n_seg ≥ cap)
    ub: np.ndarray        # [n_tiles, QB, cap_pad] f64 — score upper bound
    lb: np.ndarray        # [n_tiles, QB, cap_pad] f64 — rough lower bound
                          #   (only scales the ε probability model)
    mass: np.ndarray      # [n_tiles, QB, cap_pad] f64 — expected passing
                          #   rows of the pair's cluster (ε model's m)
    valid: np.ndarray     # [n_tiles, QB, cap_pad] bool — real (q, slot) pair


@dataclasses.dataclass
class SearchPlan:
    """Everything the fetch/scan/merge stages need, produced by plan().

    Slot tables are numpy (host) when the executor needs them per tile
    (pipelined mode, disk fetch lists, adaptive shrink) and device arrays on
    the pure-RAM sync fast path — the scan stage accepts either.
    """

    q: int
    q_block: int
    n_tiles: int
    u_cap: int               # provisioned table width (post-bucketing)
    width: int               # probe table width (n_probes or t_max)
    slot_cluster: Any        # [n_tiles·u_cap]
    slot_tile: Any           # [n_tiles·u_cap]
    slot_of_probe: Any       # [Qpad, W]
    probe_ok: Any            # [Qpad, W]
    n_unique: Any            # [n_tiles]
    queries: Array           # [Q, D] original (l2 constant)
    # [Qpad, D] original dtype, tile-padded — only the pipelined per-tile
    # executor reads it, so it is built lazily (None on sync plans)
    queries_orig_pad: Optional[Array]
    queries_pad: Array       # [Qpad, D] cast to the scan dtype
    lo_pad: Array
    hi_pad: Array
    n_pruned: Array          # [Q]
    # Geometric top-n_probes candidate clusters per (padded) query — the
    # delta tier's probe-membership mask.  None when the plan was built
    # without a delta tier attached (zero overhead on frozen serving).
    geo_probes: Optional[Array] = None   # [Qpad, T] int32
    geo_valid: Optional[Array] = None    # [Qpad, T] bool
    # Expected per-cluster generation vector at plan time (layout v3 disk
    # tier) — every fetch of this batch carries it so no cache layer can
    # silently serve a block from before the last republish.
    gens: Optional[np.ndarray] = None    # [K] int64
    # Immutable view of the RAM delta segment captured at plan(): the batch
    # scans exactly this set of delta rows/tombstones regardless of
    # concurrent appends (appends land in the next batch's snapshot).
    delta_snap: Any = None
    # Per-query chosen partition-catalog entry (−1 = flat path); None when
    # the index has no catalog or partitions are off.  Drives the planner's
    # probe remap and the partition/flat scanned-row accounting.
    route: Optional[np.ndarray] = None  # [Q] int32
    # Per-tile work items, built lazily by tile_work() (consumers: the
    # BlockStore fetch stage's per-tile novel-cluster lists, fetch routing
    # diagnostics, multi-host cache sharding).
    tiles: Optional[List[TileWork]] = None
    # Per-batch operand cache (BlockStore fetch path): cluster id → host
    # record, filled as tiles' fetches land; later tiles of the batch that
    # share the cluster assemble from these records instead of re-crossing
    # the store.  Dropped with the plan.
    operands: Optional[Dict[int, dict]] = None
    # Bound-driven termination state (None when the knob is off); built by
    # _prepare_termination before any fetch list exists, so the permuted
    # best-bound-first slot order propagates to fetch/prefetch for free.
    term: Optional[TermState] = None
    # Per-batch (cid, gen) fetch-accounting set: blocks_fetched counts each
    # distinct block once per batch even when an eviction/invalidation race
    # makes a later tile re-pull a block an earlier tile already fetched
    # (the device-cache gap-refetch double-count fix).
    fetched_keys: Optional[set] = None

    def tile_work(self) -> List[TileWork]:
        """Materializes (and caches) the per-tile work items with their
        novel-cluster fetch lists.  Requires a host plan (numpy tables)."""
        if self.tiles is None:
            sc = np.asarray(self.slot_cluster).reshape(
                self.n_tiles, self.u_cap
            )
            nu = np.asarray(self.n_unique)
            fetches = probes_lib.tile_fetch_lists(sc, nu, self.u_cap)
            releases = probes_lib.tile_release_lists(sc, nu, self.u_cap)
            self.tiles = [
                TileWork(tile=i, slot_cluster=sc[i], n_unique=int(nu[i]),
                         fetch=fetches[i], release=releases[i])
                for i in range(self.n_tiles)
            ]
        return self.tiles


@dataclasses.dataclass
class PendingSearch:
    """A batch started by :meth:`SearchEngine.submit` — its plan plus any
    tile gathers already in flight.  Finish with
    :meth:`SearchEngine.result`."""

    plan: SearchPlan
    inflight: Optional[Dict] = None


@dataclasses.dataclass
class EngineStats:
    """Per-engine execution counters (the bench reads these)."""

    batches: int = 0
    pipelined_batches: int = 0
    tiles_scanned: int = 0
    # jit cache misses for the scan stage: +1 whenever this engine dispatches
    # a (shape, backend, ...) scan signature no engine in the process has
    # compiled before — the bench's bounded-recompile gate.
    scan_compilations: int = 0
    # fetch-stage overlap accounting (pipelined disk tier)
    io_wait_s: float = 0.0    # time execute() blocked on gather_wait
    io_total_s: float = 0.0   # submit→completion span of every gather
    last_u_cap: int = 0
    u_cap_hist: Dict[int, int] = dataclasses.field(default_factory=dict)
    # BlockStore fetch path accounting
    blocks_fetched: int = 0   # per-cluster blocks pulled through the store
    blocks_reused: int = 0    # slots served from the per-batch operand
    #                           cache instead of being re-assembled/re-put
    # degradation accounting: batches completed while the store reported a
    # non-closed peer circuit (results stay bit-identical — the fallback
    # serves the same records — but the fleet should know it ran degraded)
    degraded_batches: int = 0
    # batches whose result folded a non-empty RAM delta segment (live
    # serving); frozen-checkpoint serving keeps this at 0
    delta_folds: int = 0
    # batches that skipped the delta scan because the segment's resident
    # attribute summary proved no live delta row can pass any query's
    # filter (results identical; only the scan is saved)
    delta_skips: int = 0
    # bound-driven termination: (query, slot) pairs dropped before their
    # segment was scanned — provably (upper bound below the running kth) or
    # probabilistically (ε mode) — and whole slot segments skipped because
    # every surviving pair in them was already terminated
    probes_terminated: int = 0
    term_segments_skipped: int = 0
    # partition plane: queries routed to a catalog entry / constrained
    # queries that fell back to the flat layout, and cold-scan row counts
    # split by which path the query took
    partition_hits: int = 0
    partition_fallbacks: int = 0
    partition_rows_scanned: int = 0
    flat_rows_scanned: int = 0
    # delta folds skipped by the per-attribute running interval envelope
    # (satellite of the summary-based delta_skips; also counted there)
    delta_interval_skips: int = 0
    # the scan kernel's top-k fold: insertion passes run, and k passes per
    # grid step (what a fold of fixed length runs); passes ÷ k_passes is the
    # share of that fold the data needed.  Summed from the kernel's device
    # counts when the engine's metrics are read, finished scans only.
    fold_passes: int = 0
    fold_k_passes: int = 0

    @property
    def overlap_ratio(self) -> float:
        """Fraction of gather time hidden behind compute (1 = fully
        overlapped, 0 = fully serial)."""
        if self.io_total_s <= 0:
            return 0.0
        return max(0.0, 1.0 - self.io_wait_s / self.io_total_s)


# Process-wide registry of scan-stage signatures that have been dispatched;
# mirrors the underlying jit cache (which is also process-wide), so a new key
# here == a real XLA compilation.
_SCAN_KEYS: set = set()

# Scans whose fold counts wait unread before the engine sums the finished
# ones itself (a server whose metrics nobody reads).
_FOLD_PENDING_MAX = 1024


def scan_compile_count() -> int:
    """Number of distinct scan-stage compilations this process has run."""
    return len(_SCAN_KEYS)


def u_cap_buckets(full_cap: int, lo: int = 8,
                  ladder: str = "pow2") -> Tuple[int, ...]:
    """The fixed u_cap bucket set for ``full_cap``.

    ``ladder="pow2"``: ``(8, 16, 32, ..., full_cap)`` — doubling widths from
    ``lo`` with the exact worst-case cap appended, so every observed unique
    count maps to a bucket and the bucket count (= max scan compilations) is
    ``log2(full_cap/8) + O(1)``.

    ``ladder="fine"`` additionally inserts the ×1.5 midpoint between each
    power-of-two pair (``8, 12, 16, 24, 32, 48, ...``): a batch observing 38
    uniques scans a 48-slot table instead of 64 — the XLA executor's cost is
    linear in table width, so the midpoints buy back up to ~25% of the slot
    scans right above a bucket edge, at the price of ~2× the worst-case
    compile count (still bounded; measured in BENCH_search.json's
    ``u_cap_ladder_ab``).
    """
    if ladder not in ("pow2", "fine"):
        raise ValueError(f"ladder must be 'pow2'|'fine', got {ladder!r}")
    caps = []
    b = lo
    while b < full_cap:
        caps.append(b)
        if ladder == "fine":
            mid = (b * 3) // 2
            if mid < full_cap:
                caps.append(mid)
        b *= 2
    caps.append(full_cap)
    return tuple(sorted(set(caps)))


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


class SearchEngine:
    """Single entry point for the tiled fused search, both tiers.

    Knobs (latency ↔ throughput):
      * ``pipeline`` — ``"off"``: one whole-batch fetch + one scan (lowest
        per-batch latency when the data is RAM-resident).  ``"on"``: per-tile
        double-buffered fetch/scan overlap (disk-tier throughput; identical
        results).  ``"auto"``: on iff the index pages from disk.
      * ``pipeline_depth`` — gathers kept in flight ahead of the scan
        (2 = classic double buffering; more overlaps deeper but holds more
        gathered tiles in host memory).
      * ``adaptive_u_cap`` — provision the slot table from the observed
        post-prune unique counts (power-of-two buckets, bounded recompiles)
        instead of the worst case.  ``u_cap`` pins the width instead.
      * ``q_block`` — query-tile height: smaller tiles → finer pipeline
        grain (more IO/compute overlap) but more per-tile dispatches.  With
        the operand cache, finer grain no longer pays re-assembly for the
        clusters tiles share.
      * ``operand_cache`` — per-batch reuse of fetched cluster blocks
        (BlockStore path only): each block crosses the store (ring hop,
        cache lock, mmap read) once per batch; tiles that share it assemble
        straight from the batch-local records on the fetch worker
        (``"auto"``/``"on"``/``"off"``; ``blocks_reused`` counts slots
        served from the batch cache).
      * ``u_cap_ladder`` — ``"pow2"`` (default) or ``"fine"`` (×1.5
        midpoints): finer buckets waste fewer pad-slot scans right above a
        bucket edge at ~2× the bounded compile count.
      * ``t_max`` — static widening cap, or ``"auto"`` to pick the per-batch
        cap from the summaries' expected passing mass (bucketed ×2/×4/×8).
      * ``termination`` — bound-driven early termination. ``"exact"``: scan
        each tile's probes best-bound-first in segments and drop remaining
        probes whose score upper bound is provably below the running kth —
        bit-identical results, fewer slot scans. ``"bounded"`` with
        ``epsilon``: additionally drop probes whose probability of
        contributing a top-k row (bound + summary-mass model) is ≤ ε — a
        recall-bounded speed tier (recall@k ≥ 1 − ε per dropped-probe
        model; gated empirically in BENCH_search.json). ``None`` (default)
        keeps the unterminated executors byte-for-byte.

    ``index`` needs the resident surface (``spec / centroids / counts /
    n_clusters / store_dtype / quantized / summaries``) plus one fetch
    source: resident ``vectors/attrs/ids/norms/scales`` (RAM tier), a
    ``blockstore`` (its own, or passed explicitly — e.g. a
    :class:`~repro.core.blockstore.ShardedBlockStore`), or a legacy
    ``gather`` method (``gather_submit``/``gather_wait`` unlock the async
    fetch).
    """

    def __init__(self, index, *, k: int, n_probes: int, q_block: int = 64,
                 v_block: int = 256, u_cap: Optional[int] = None,
                 backend: Optional[str] = None,
                 gather_fn: Optional[Callable] = None,
                 blockstore=None,
                 prune: str = "auto", t_max=None,
                 pipeline: str = "auto", pipeline_depth: int = 2,
                 adaptive_u_cap: Optional[bool] = None,
                 u_cap_bucket_set: Optional[Tuple[int, ...]] = None,
                 u_cap_ladder: str = "pow2",
                 operand_cache: str = "auto",
                 delta=None,
                 device_cache=None,
                 termination: Optional[str] = None,
                 epsilon: float = 0.0,
                 partitions: str = "auto"):
        if termination not in (None, "exact", "bounded"):
            raise ValueError(f"termination must be None|'exact'|'bounded', "
                             f"got {termination!r}")
        if not (0.0 <= float(epsilon) < 1.0):
            raise ValueError(f"epsilon must be in [0, 1), got {epsilon!r}")
        if epsilon > 0.0 and termination != "bounded":
            raise ValueError("epsilon > 0 requires termination='bounded'")
        if pipeline not in ("auto", "on", "off"):
            raise ValueError(f"pipeline must be 'auto'|'on'|'off', got "
                             f"{pipeline!r}")
        if pipeline_depth < 1:
            raise ValueError("pipeline_depth must be >= 1")
        if operand_cache not in ("auto", "on", "off"):
            raise ValueError(f"operand_cache must be 'auto'|'on'|'off', got "
                             f"{operand_cache!r}")
        if isinstance(t_max, str) and t_max != "auto":
            raise ValueError(f"t_max must be an int, 'auto' or None, got "
                             f"{t_max!r}")
        if partitions not in ("auto", "on", "off"):
            raise ValueError(f"partitions must be 'auto'|'on'|'off', got "
                             f"{partitions!r}")
        self.partitions = partitions
        # filter-traffic recorder (partition-attribute choice input) and the
        # planner's base-width array views / device members table, built
        # lazily on the first planned batch
        self._traffic = None
        self._base_memo = None
        self._members_memo = None
        self.index = index
        self.k = k
        self.n_probes = n_probes
        self.q_block = q_block
        self.v_block = v_block
        self.u_cap = u_cap
        self.prune = prune
        self.t_max = t_max
        self.pipeline_depth = pipeline_depth
        self.u_cap_bucket_set = u_cap_bucket_set
        if u_cap_ladder not in ("pow2", "fine"):
            raise ValueError(f"u_cap_ladder must be 'pow2'|'fine', got "
                             f"{u_cap_ladder!r}")
        self.u_cap_ladder = u_cap_ladder
        self.operand_cache = operand_cache
        self.backend = backend or (
            "pallas" if jax.default_backend() == "tpu" else "xla"
        )
        # fetch source: explicit gather_fn wins (the pre-BlockStore path,
        # kept as the A/B baseline and for custom pagers); otherwise an
        # explicit or index-provided BlockStore; otherwise the index's own
        # legacy pager; otherwise the resident arrays (RAM tier).
        self._store = None
        if gather_fn is not None:
            self._gather_fn = gather_fn
        else:
            self._store = (blockstore if blockstore is not None
                           else getattr(index, "blockstore", None))
            self._gather_fn = (
                self._store_gather if self._store is not None
                else getattr(index, "gather", None)
            )
        self._bspec = (
            blockstore_lib.BlockSpec.from_index(index)
            if self._store is not None else None
        )
        if operand_cache == "on" and self._store is None:
            raise ValueError("operand_cache='on' needs a BlockStore fetch "
                             "path (disk tier or explicit blockstore=)")
        # cross-batch device-resident block cache: explicit instance or byte
        # budget wins; otherwise the index's attached cache
        # (make_fused_search_fn(device_cache_mb=...) sets index.device_cache)
        dc = (device_cache if device_cache is not None
              else getattr(index, "device_cache", None))
        if isinstance(dc, (int, float)):
            from repro.core.devicecache import DeviceBlockCache

            if self._store is None:
                raise ValueError("device_cache needs a BlockStore fetch "
                                 "path (disk tier or explicit blockstore=)")
            heat = getattr(getattr(index, "cache", None), "probe_heat", None)
            dc = DeviceBlockCache(self._bspec, int(dc), heat_fn=heat)
        if dc is not None and self._store is None:
            raise ValueError("device_cache needs a BlockStore fetch path "
                             "(disk tier or explicit blockstore=)")
        self._device_cache = dc
        # async pair available iff the source IS the index's legacy pager
        self._async_src = (
            index if (self._store is None
                      and self._gather_fn is not None
                      and getattr(index, "gather_submit", None) is not None
                      and self._gather_fn == index.gather)
            else None
        )
        self.pipeline = (
            pipeline if pipeline != "auto"
            else ("on" if self._gather_fn is not None else "off")
        )
        # adaptive provisioning defaults on when the caller didn't pin u_cap
        self.adaptive_u_cap = (
            (u_cap is None) if adaptive_u_cap is None else adaptive_u_cap
        )
        if self.adaptive_u_cap and u_cap is not None:
            raise ValueError("u_cap and adaptive_u_cap are exclusive")
        # RAM delta tier: explicit wins; otherwise the index's attached tier
        # (DiskIVFIndex.delta / make_fused_search_fn(delta_budget_mb=...)).
        self._delta = delta
        # Bound-driven early termination: "exact" drops only provably-losing
        # probes (bitwise-identical results); "bounded" additionally drops
        # probes whose win probability under the bound model is ≤ epsilon.
        self.termination = termination
        self.epsilon = float(epsilon)
        self._bounds_cache = None  # (key, ClusterBounds) lazy-build memo
        # per-stage fixed-bucket latency histograms (plan and its parts,
        # fetch, scan or scan_dispatch, merge_dispatch, delta_fold),
        # appended to metrics_text() for the Prometheus scrape
        self._stage_hist: Dict[str, StageHistogram] = {}
        self.stats = EngineStats()
        # the scans' top-k insertion passes, still on the device
        self._fold_pending: List[Tuple[Array, int]] = []
        self._fold_lock = threading.Lock()

    def _observe_stage(self, stage: str, seconds: float):
        hist = self._stage_hist.get(stage)
        if hist is None:
            hist = self._stage_hist[stage] = StageHistogram()
        hist.observe(seconds)

    def _stage(self, stage: str) -> span:
        """Span ``repro.engine.<stage>``, timed into the stage's histogram."""
        return span(f"engine.{stage}",
                    functools.partial(self._observe_stage, stage))

    def _delta_tier(self):
        if self._delta is not None:
            return self._delta
        return getattr(self.index, "delta", None)

    # ---- partition routing (plan-side) ----
    def _resolve_partitions(self):
        """Resolves the ``partitions`` knob against the index's catalog.

        Returns the :class:`~repro.core.partitions.PartitionCatalog` to
        route with, or None for the flat-only planner.  ``"auto"`` routes
        iff the index carries a catalog; ``"on"`` demands one; ``"off"``
        never routes (bit-identical to the pre-partition planner)."""
        cat = getattr(self.index, "partitions", None)
        if self.partitions == "off":
            return None
        if self.partitions == "on" and cat is None:
            raise ValueError(
                "partitions='on' but the index has no partition catalog — "
                "save the checkpoint with layout v4 "
                "(save_index(partitions=build_partitions(...))) or use "
                "partitions='auto'"
            )
        return cat

    def _base_views(self, cat, summ):
        """Base-width planner views of centroids/counts/summaries.

        The disk tier's resident arrays are already base-width; a RAM index
        with attached partitions carries the sub rows inline (scan targets),
        and planning over them would probe duplicated sub centroids — so the
        planner slices to ``[:n_base]``, memoized until the arrays swap."""
        index = self.index
        cents = index.centroids
        nb = cat.n_base
        if int(np.shape(cents)[0]) == nb:
            return cents, index.counts, summ
        memo = self._base_memo
        if memo is not None and memo[0] == id(cents):
            return memo[1], memo[2], (memo[3] if summ is not None else None)
        c = cents[:nb]
        cnt = index.counts[:nb]
        s = None
        if summ is not None:
            s = dataclasses.replace(
                summ, amin=summ.amin[:nb], amax=summ.amax[:nb],
                hist=summ.hist[:nb],
            )
        self._base_memo = (id(cents), c, cnt, s)
        return c, cnt, s

    def _members_device(self, cat):
        """The catalog's [E, K_base] member table as a device array (the
        plan-stage remap operand), memoized per catalog object."""
        memo = self._members_memo
        if memo is not None and memo[0] == id(cat):
            return memo[1]
        m = jnp.asarray(cat.members, jnp.int32)
        self._members_memo = (id(cat), m)
        return m

    def _route_partitions(self, cat, fspec: FilterSpec):
        """Host-side narrowest-subsuming-entry routing + traffic recording.

        Returns ``(route, route_entry, members)`` — the [Q] entry choice
        (−1 = flat) and the remap operands for :func:`plan_fused_tiled` —
        or ``(None, None, None)`` when no catalog is active."""
        lo_np = np.asarray(fspec.lo)
        hi_np = np.asarray(fspec.hi)
        if self.partitions != "off":
            if self._traffic is None:
                from repro.core.partitions import FilterTrafficRecorder

                self._traffic = FilterTrafficRecorder(int(lo_np.shape[-1]))
            self._traffic.observe(lo_np, hi_np)
        if cat is None:
            return None, None, None
        route = cat.route(lo_np, hi_np)  # [Q] int32
        hits = int(np.sum(route >= 0))
        self.stats.partition_hits += hits
        # fallbacks: queries that DO constrain some attribute but no catalog
        # entry subsumes them (unfiltered queries are not "fallbacks" — the
        # flat path is simply their layout)
        nonvoid = np.all(lo_np <= hi_np, axis=-1)  # [Q, T]
        narrowed = np.any(
            (lo_np > summaries_lib.ATTR_MIN)
            | (hi_np < summaries_lib.ATTR_MAX), axis=-1,
        )
        constrained = np.any(nonvoid & narrowed, axis=-1)  # [Q]
        self.stats.partition_fallbacks += int(
            np.sum(constrained & (route < 0))
        )
        if hits == 0:
            return route, None, None  # keep the flat plan signature
        return route, jnp.asarray(route), self._members_device(cat)

    @property
    def traffic(self):
        """The engine's filter-traffic recorder (partition-attribute choice
        input for rebuilds); None until a batch has been planned."""
        return self._traffic

    # ---- plan ----
    def plan(self, queries: Array, fspec: FilterSpec) -> SearchPlan:
        """Plan stage: jitted resident-state plan + host-side provisioning.

        Always plans at the sound worst-case table width (one compile); with
        ``adaptive_u_cap`` the tables are then shrunk to the smallest
        power-of-two bucket covering the observed per-tile unique counts.
        """
        with self._stage("plan"):
            with self._stage("plan.prep"):
                index = self.index
                q = queries.shape[0]
                qb = min(self.q_block, round_up(q, 8))
                summ = resolve_prune(index, self.prune)
                # Partition routing: probing geometry (centroid top-k,
                # summaries, widening, bounds) always runs over the BASE
                # clusters — sub ids only enter via the plan-stage probe remap
                # below, so an index with a catalog plans exactly like the flat
                # index for unrouted queries.
                cat = self._resolve_partitions()
                # a RAM index with attached sub-partitions carries them inline
                # in the per-cluster arrays — the planner slices to base width
                # even with routing off, else the centroid top-k would probe
                # the subs' duplicated centroids (not the flat plan)
                cat_any = getattr(index, "partitions", None)
                centroids = index.centroids
                counts = index.counts
                kc = index.n_clusters
                if cat_any is not None:
                    kc = cat_any.n_base
                    centroids, counts, summ = self._base_views(cat_any, summ)
                route, route_entry, members = self._route_partitions(
                    cat, fspec)
                # Capture an immutable view of the RAM delta segment for this
                # batch, and plan with tombstone/append-adjusted cluster
                # counts: a rebuilt index would see those counts, and
                # centroid_scores masks empty clusters by count — parity
                # requires the live planner to agree.
                tier = self._delta_tier()
                snap = tier.snapshot() if tier is not None else None
                if snap is not None:
                    adj = tier.count_adjustment(kc)
                    if adj is not None:
                        counts = counts + jnp.asarray(adj)
                t_max = self.t_max
                if t_max == "auto":
                    # summary-driven widening: bucketed per batch from the
                    # expected passing mass, so a selective batch widens and an
                    # unfiltered one plans exactly like t_max=None
                    # (bit-identical)
                    t_max = resolve_auto_t_max(
                        summ, counts, fspec.lo, fspec.hi, self.n_probes, kc
                    )
                if t_max is not None:
                    if t_max < self.n_probes:
                        raise ValueError(
                            f"t_max={t_max} < n_probes={self.n_probes}"
                        )
                    t_max = min(t_max, kc)
                    if summ is None or t_max == self.n_probes:
                        # widening is only meaningful with pruning
                        t_max = None
                width = self.n_probes if t_max is None else t_max
                # remapped probes draw from base ∪ sub ids, so the per-tile
                # unique count can exceed the base cluster count — provision
                # for the full id space or the dedup's overflow drop would
                # break parity
                k_total = kc + (cat.n_subs if cat is not None else 0)
                full_cap = min(qb * width, k_total)
                cap = full_cap if self.u_cap is None else self.u_cap
                cast_dtype = (
                    np.dtype(np.float32) if index.quantized
                    else np.dtype(index.store_dtype)
                )
                # The sync RAM fast path needs no host view of the tables; the
                # pipelined / disk paths (per-tile slices, fetch lists) do.
                # The adaptive provisioner alone only needs the tiny [n_tiles]
                # unique counts — the full tables come to host iff a shrink
                # happens.
                need_host = (self.pipeline == "on"
                             or self._gather_fn is not None
                             or self.termination is not None)
            with self._stage("plan.device"):
                (slot_cluster, slot_tile, slot_of_probe, probe_ok, n_unique,
                 queries_pad, lo_pad, hi_pad, n_pruned, geo_probes,
                 geo_valid) = plan_fused_tiled(
                    centroids, counts, queries, fspec.lo, fspec.hi,
                    metric=index.spec.metric, n_probes=self.n_probes,
                    q_block=qb, u_cap=cap, cast_dtype=cast_dtype,
                    summaries=summ, t_max=t_max,
                    route_entry=route_entry, members=members,
                )
                if self.adaptive_u_cap or need_host:
                    # the plan's first host read: it waits for the
                    # plan program to finish on the device
                    n_unique = np.asarray(n_unique)
            with self._stage("plan.tables"):
                qpad = queries_pad.shape[0]
                n_tiles = qpad // qb
                plan = SearchPlan(
                    q=q, q_block=qb, n_tiles=n_tiles, u_cap=cap, width=width,
                    slot_cluster=slot_cluster, slot_tile=slot_tile,
                    slot_of_probe=slot_of_probe, probe_ok=probe_ok,
                    n_unique=n_unique, queries=queries,
                    queries_orig_pad=(
                        probes_lib.pad_to_tiles(queries, qb)
                        if self.pipeline == "on" else None
                    ),
                    queries_pad=queries_pad, lo_pad=lo_pad, hi_pad=hi_pad,
                    n_pruned=n_pruned,
                    geo_probes=(geo_probes if snap is not None else None),
                    geo_valid=(geo_valid if snap is not None else None),
                    gens=self._plan_gens(),
                    delta_snap=snap,
                    route=route,
                )
                if self.adaptive_u_cap:
                    self._provision(plan)
                if need_host:
                    self._host_tables(plan)
                if self.termination is not None:
                    # reorders the slot tables best-bound-first and attaches
                    # the TermState; must run before any fetch list / TileWork
                    # exists so fetch order and prefetch follow the scan order
                    self._prepare_termination(plan, summ, counts)
                self.stats.last_u_cap = plan.u_cap
                self.stats.u_cap_hist[plan.u_cap] = (
                    self.stats.u_cap_hist.get(plan.u_cap, 0) + 1
                )
        return plan

    def _plan_gens(self) -> Optional[np.ndarray]:
        """Per-cluster expected-generation vector for this batch's fetches
        (None on pre-v3 / RAM indexes — every gen is implicitly 0)."""
        g = getattr(self.index, "gens", None)
        return None if g is None else np.asarray(g)

    def _host_tables(self, plan: SearchPlan):
        plan.slot_cluster = np.asarray(plan.slot_cluster)
        plan.slot_tile = np.asarray(plan.slot_tile)
        plan.slot_of_probe = np.asarray(plan.slot_of_probe)
        plan.probe_ok = np.asarray(plan.probe_ok)
        plan.n_unique = np.asarray(plan.n_unique)

    def _provision(self, plan: SearchPlan):
        """Adaptive u_cap: shrink the slot tables to the smallest bucket
        covering the observed per-tile unique counts.

        Sound by construction — the bucket is ≥ every tile's true unique
        count, so no probe is dropped and results stay bit-identical to the
        worst-case table; only pad slots (repeats of each tile's last unique
        id) are cut.  Only the [n_tiles] unique counts are synced to host
        to pick the bucket; the full tables follow only when a shrink
        actually happens (bucket == full leaves a device-only plan alone).
        """
        full = plan.u_cap
        plan.n_unique = np.asarray(plan.n_unique)
        max_u = max(int(plan.n_unique.max(initial=1)), 1)
        buckets = self.u_cap_bucket_set or u_cap_buckets(
            full, ladder=self.u_cap_ladder
        )
        bucket = next((b for b in sorted(buckets) if b >= max_u), full)
        bucket = min(bucket, full)
        if bucket == full:
            return
        self._host_tables(plan)
        sc = plan.slot_cluster.reshape(plan.n_tiles, full)[:, :bucket]
        plan.slot_cluster = np.ascontiguousarray(sc).reshape(-1)
        plan.slot_tile = np.repeat(
            np.arange(plan.n_tiles, dtype=np.int32), bucket
        )
        # re-base flat probe→slot pointers from stride `full` to `bucket`;
        # overflow-clipped junk pointers of not-ok probes stay in range.
        t_idx, s = divmod(plan.slot_of_probe, full)
        plan.slot_of_probe = (
            t_idx * bucket + np.minimum(s, bucket - 1)
        ).astype(np.int32)
        plan.u_cap = bucket

    # ---- bound-driven termination (plan-side) ----
    def _resolve_bounds(self):
        """The per-cluster :class:`~repro.core.summaries.ClusterBounds`:
        the index's precomputed row (disk tier; ``storage.load_bounds``),
        else lazily built from the resident flat lists and memoized until
        the arrays are swapped (refresh)."""
        index = self.index
        b = getattr(index, "bounds", None)
        if b is not None:
            return b
        vectors = getattr(index, "vectors", None)
        if vectors is None:
            raise ValueError(
                "termination needs per-cluster score bounds, but the index "
                "has neither a precomputed `bounds` attribute nor resident "
                "vectors to build one from. Re-save the index with this "
                "version (save_index now writes bounds_radius.npy / "
                "bounds_slack.npy) or attach storage.load_bounds() output."
            )
        key = (id(vectors), id(getattr(index, "scales", None)))
        cached = self._bounds_cache
        if cached is not None and cached[0] == key:
            return cached[1]
        b = summaries_lib.build_bounds(
            index.centroids, vectors, index.ids,
            getattr(index, "norms", None), getattr(index, "scales", None),
        )
        self._bounds_cache = (key, b)
        return b

    def _prepare_termination(self, plan: SearchPlan, summ, counts):
        """Builds the batch's :class:`TermState` and reorders the slot
        tables best-bound-first.

        Per (query, slot) pair an upper bound on any row's kernel-space
        score is derived from resident state only: the centroid inner
        product plus a Cauchy–Schwarz ``‖q‖·radius`` term (dot), or the
        ``‖q‖² − max(d − radius, 0)²`` ball bound shifted by the cluster's
        norm slack (l2, pre-fixup space).  The bound is over the *stored*
        rows (SQ8 measured dequantized), widened by a dtype-aware rounding
        margin so float-accumulation noise can never flip a provable drop.
        Runs after adaptive provisioning (tables at their final width) and
        before any fetch list exists (fetch order follows the permutation).
        """
        index = self.index
        qb, cap, n_tiles = plan.q_block, plan.u_cap, plan.n_tiles
        qpad = qb * n_tiles
        bounds = self._resolve_bounds()
        sc = np.asarray(plan.slot_cluster).reshape(n_tiles, cap)
        cat = self._resolve_partitions()
        if cat is not None:
            # routed slots hold sub-partition ids; centroids / bounds /
            # summary mass are indexed base-width, and a parent's bound
            # soundly covers every sub (subset of its rows, same centroid)
            sc = cat.to_base(sc)

        # which (tile, query-row, slot) pairs are real probes
        sop = np.asarray(plan.slot_of_probe)
        pok = np.asarray(plan.probe_ok)
        tt, ss = np.divmod(sop, cap)
        qi = np.broadcast_to(
            (np.arange(qpad, dtype=np.int32) % qb)[:, None], sop.shape
        )
        valid = np.zeros((n_tiles, qb, cap), bool)
        valid[tt[pok], qi[pok], ss[pok]] = True

        # per-pair score bounds from the CAST queries (the kernel casts to
        # the store dtype before the matmul — bounding the cast query keeps
        # the bound sound for exactly what the kernel scores)
        qt = np.asarray(plan.queries_pad).astype(np.float32)
        qt = qt.reshape(n_tiles, qb, -1)
        C = np.asarray(index.centroids, dtype=np.float32)
        csel = C[sc]                                   # [n_tiles, cap, D]
        rsel = np.asarray(bounds.radius, np.float32)[sc][:, None, :]
        metric = index.spec.metric
        if metric == "dot":
            cs = np.einsum("tqd,tsd->tqs", qt, csel)
            qn = np.linalg.norm(qt, axis=-1)[:, :, None]
            ub = cs + qn * rsel
            lb = cs - qn * rsel
        else:  # l2 — kernel space 2q·x̂ − norms_row (‖q‖² not yet folded)
            qt64 = qt.astype(np.float64)
            c64 = csel.astype(np.float64)
            # ‖q − c‖ in float64: the expanded form cancels catastrophically
            # in f32 when q ≈ c, and an under-estimated d inflates nothing
            # but an OVER-estimated one would break the upper bound
            cs64 = np.einsum("tqd,tsd->tqs", qt64, c64)
            q2 = np.sum(qt64 * qt64, axis=-1)[:, :, None]
            c2 = np.sum(c64 * c64, axis=-1)[:, None, :]
            d = np.sqrt(np.maximum(q2 - 2.0 * cs64 + c2, 0.0))
            near = np.maximum(d - rsel, 0.0)
            ssel = np.asarray(bounds.slack, np.float32)[sc][:, None, :]
            ub = q2 - near * near + ssel
            lb = q2 - (d + rsel) ** 2
        # rounding margin: the kernel accumulates in f32 (operands possibly
        # 16-bit) — widen so accumulation noise can't beat the bound
        itemsize = np.dtype(index.store_dtype).itemsize
        tol = 1e-2 if (not index.quantized and itemsize == 2) else 1e-4
        # f64 state: the ε model subtracts the running kth (NEG_INF when a
        # query's top-k isn't full yet), which overflows in f32
        ub = ub.astype(np.float64) + (1e-3 + tol * np.abs(ub))
        lb = lb.astype(np.float64)

        # ε model's mass: expected passing rows of the pair's cluster under
        # the query's filter (live counts when summaries are off)
        if summ is not None:
            ep = np.asarray(summaries_lib.expected_passing(
                summ, plan.lo_pad, plan.hi_pad, counts
            ))
            mass = np.take_along_axis(
                ep.reshape(n_tiles, qb, -1), sc[:, None, :], axis=2
            )
        else:
            cnt = np.asarray(counts, np.float32)[sc][:, None, :]
            mass = np.broadcast_to(cnt, (n_tiles, qb, cap)).copy()

        # best-bound-first: permute each tile's live slots by descending
        # max-over-queries upper bound, remap probe pointers, co-permute
        slot_bound = np.where(valid, ub, -np.inf).max(axis=1)
        sc_flat, sop_new, perm = probes_lib.bound_order(
            plan.slot_cluster, plan.n_unique, plan.slot_of_probe,
            slot_bound, cap,
        )
        plan.slot_cluster = sc_flat
        plan.slot_of_probe = sop_new
        pq = perm[:, None, :]
        ub = np.take_along_axis(ub, pq, axis=2)
        lb = np.take_along_axis(lb, pq, axis=2)
        mass = np.take_along_axis(mass, pq, axis=2)
        valid = np.take_along_axis(valid, pq, axis=2)

        # segment the slot axis: ~4 segments per tile, widths a multiple of
        # 4 so every (bucket, seg) scan shape comes from a bounded set
        seg = max(4, ((-(-cap // 4) + 3) // 4) * 4)
        n_seg = -(-cap // seg)
        cap_pad = n_seg * seg
        if cap_pad > cap:
            padw = ((0, 0), (0, 0), (0, cap_pad - cap))
            ub = np.pad(ub, padw, constant_values=-np.inf)
            lb = np.pad(lb, padw, constant_values=-np.inf)
            mass = np.pad(mass, padw, constant_values=0.0)
            valid = np.pad(valid, padw, constant_values=False)
        plan.term = TermState(
            epsilon=(self.epsilon if self.termination == "bounded"
                     else 0.0),
            seg=seg, n_seg=n_seg, cap=cap,
            ub=ub, lb=lb, mass=mass, valid=valid,
        )

    # ---- fetch ----
    @property
    def blockstore(self):
        """The BlockStore the fetch stage routes through (None when the
        engine reads resident arrays or a legacy gather_fn)."""
        return self._store

    @property
    def _use_operand_cache(self) -> bool:
        return self._store is not None and self.operand_cache != "off"

    @property
    def device_cache(self):
        """The cross-batch device-resident block cache (None when off)."""
        return self._device_cache

    def _note_device_hits(self, n: int):
        """Tells a sharded store how many blocks the device cache served —
        fetches that never happened, i.e. avoided peer RPCs / disk reads."""
        if n <= 0:
            return
        note = getattr(self._store, "note_device_hits", None)
        if note is not None:
            note(n)

    def _count_fetched(self, plan: Optional[SearchPlan], cids):
        """``blocks_fetched`` accounting on fetch paths with a reuse layer
        (operand / device cache): deduped per batch by ``(cluster, gen)``.
        An eviction or partial invalidation between a tile's submit and its
        assembly makes the gap/missing fallbacks re-pull a block an earlier
        tile of the same batch already fetched — the counter reports
        distinct blocks, so a composed-tile memo hit after a partial
        invalidation no longer double-counts."""
        if plan is None:
            self.stats.blocks_fetched += len(cids)
            return
        if plan.fetched_keys is None:
            plan.fetched_keys = set()
        gens = plan.gens
        for c in cids:
            cid = int(c)
            key = (cid, int(gens[cid]) if gens is not None else 0)
            if key not in plan.fetched_keys:
                plan.fetched_keys.add(key)
                self.stats.blocks_fetched += 1

    def _store_gather(self, slot_cluster, gens: Optional[np.ndarray] = None,
                      plan: Optional[SearchPlan] = None):
        """Whole-list gather through the BlockStore protocol — the sync
        executor's fetch stage (same record ordering, and therefore cache
        behavior, as the pre-protocol pager).  ``gens`` is the full [K]
        expected-generation vector; each fetched cluster carries its entry
        so no cache layer can serve a pre-republish block."""
        flat = np.asarray(slot_cluster).reshape(-1)
        uniq, local = blockstore_lib.first_need_unique(flat)
        g = None if gens is None else gens[uniq]
        if self._device_cache is not None:
            return self._device_gather(flat, uniq, local, gens, plan=plan)
        recs = self._store.get(uniq, gens=g)
        self.stats.blocks_fetched += len(recs)
        return blockstore_lib.assemble_blocks(flat, uniq, local, recs,
                                              self._bspec)

    def _device_gather(self, flat, uniq, local, gens,
                       plan: Optional[SearchPlan] = None):
        """Device-cache-aware gather: resident clusters are served straight
        from the device cache (no store fetch, no host assembly, no H2D);
        only the misses cross the BlockStore, are device-put once and
        admitted.  The batch's blocks are composed on device with the host
        path's exact padding, so results stay bit-identical."""
        dc = self._device_cache
        egens = None if gens is None else gens[uniq]
        s = flat.shape[0]
        tile = dc.get_tile(uniq, s, egens)
        if tile is not None:  # exact repeat: the composed blocks, verbatim
            self._note_device_hits(len(uniq))
            self.stats.blocks_reused += len(uniq)
            return (local.astype(np.int32),) + tile
        hits, missing = dc.get_many(uniq, egens)
        self._note_device_hits(len(hits))
        self.stats.blocks_reused += len(hits)
        if missing:
            marr = np.asarray(missing, np.int64)
            recs = self._store.get(
                marr, gens=None if gens is None else gens[marr]
            )
            self._count_fetched(plan, recs)
            hits.update(dc.put_records(recs))
        entries = [hits[int(c)] for c in uniq]
        blocks = dc.compose(entries, s)
        dc.put_tile(uniq, s, entries, blocks)
        return (local.astype(np.int32),) + blocks

    def _expected_gens(self, plan: SearchPlan,
                       cids) -> Optional[np.ndarray]:
        """Expected generations for a fetch list, from the plan's vector."""
        if plan.gens is None:
            return None
        return plan.gens[np.asarray(cids, np.int64)]

    def fetch(self, plan: SearchPlan):
        """Whole-batch fetch stage (sync executor): resident arrays on the
        RAM tier, one gather over the plan's slot list on the disk tier."""
        index = self.index
        if self._gather_fn is None:
            return (plan.slot_cluster, index.vectors, index.attrs, index.ids,
                    index.norms, index.scales)
        with self._stage("fetch"):
            if (self._store is not None
                    and self._gather_fn == self._store_gather):
                out = self._store_gather(plan.slot_cluster, gens=plan.gens,
                                         plan=plan)
            else:
                out = self._gather_fn(plan.slot_cluster)
        slot_cluster, vectors, attrs, ids, norms, scales = out
        return (jnp.asarray(slot_cluster), vectors, attrs, ids, norms,
                scales)

    # ---- scan + merge ----
    def _note_fold(self, fold, n_slots: int, vectors):
        """Keeps a scan's top-k insertion passes (``fold``, a device scalar;
        None off the kernel) beside the k passes per grid step that a fold
        of fixed length would run.  Nothing is read here: :meth:`metrics`
        sums the counts of the scans that have finished."""
        from repro.kernels.filtered_scan.filtered_scan import fold_steps

        if fold is None:
            return
        steps = fold_steps(int(np.shape(vectors)[1]), self.v_block)
        with self._fold_lock:
            self._fold_pending.append((fold, n_slots * steps * self.k))
        if len(self._fold_pending) >= _FOLD_PENDING_MAX:
            self._collect_fold()  # nothing reads the stats: keep it bounded

    def _collect_fold(self):
        """Adds the finished scans' insertion passes into the stats; those
        still on the device wait for the next read."""
        with self._fold_lock:
            done, wait = [], []
            for p in self._fold_pending:
                (done if p[0].is_ready() else wait).append(p)
            self._fold_pending = wait
            if done:
                passes = jax.device_get([p[0] for p in done])
                self.stats.fold_passes += sum(int(x) for x in passes)
                self.stats.fold_k_passes += sum(p[1] for p in done)

    def _count_scan(self, key: Tuple):
        if key not in _SCAN_KEYS:
            _SCAN_KEYS.add(key)
            self.stats.scan_compilations += 1

    def _scan_key(self, plan: SearchPlan, *, q: int, qpad: int, s: int,
                  q_block: int, vectors, norms, scales) -> Tuple:
        """The scan stage's jit signature: statics + argument shapes/dtypes
        of :func:`_scan_merge_tiled`.  A whole-batch call over one tile and
        a per-tile call at the same shapes produce the SAME key — they hit
        the same compiled executable, so they must count once."""
        return (
            self.backend, self.index.spec.metric, self.k, q, q_block,
            self.v_block, s, qpad, plan.width,
            np.shape(vectors), str(vectors.dtype),
            str(plan.queries_pad.dtype), tuple(plan.lo_pad.shape[1:]),
            norms is None, scales is None,
        )

    def _mask_tombstones(self, plan: SearchPlan, ids):
        """Masks the snapshot's tombstoned ids out of the cold-tier scan.

        Applied to the ids operand (not the merged result) so the scan's
        masked top-k naturally surfaces the (k+1)-th cold candidate — what a
        rebuild without the deleted rows would return."""
        snap = plan.delta_snap
        if snap is None or snap.tombstones is None:
            return ids
        from repro.core import delta as delta_lib

        return delta_lib.mask_tombstones(jnp.asarray(ids), snap.tombstones)

    def _fold_delta(self, plan: SearchPlan, res: SearchResult) -> SearchResult:
        """Merge stage, tier two: exact scan of the RAM delta segment folded
        into the cold result through the same top-k monoid (cold wins score
        ties, matching concat order in a rebuilt index's merge)."""
        snap = plan.delta_snap
        if snap is None or snap.n_rows == 0:
            return res
        with self._stage("delta_fold"):
            return self._fold_snapshot(plan, snap, res)

    def _fold_snapshot(self, plan: SearchPlan, snap,
                       res: SearchResult) -> SearchResult:
        from repro.core import delta as delta_lib

        # Per-attribute interval pre-test: the delta tier keeps a running
        # [M] lo/hi envelope over its live rows, refreshed on append — a
        # batch whose every non-void term is disjoint from the envelope on
        # ANY attribute provably matches zero delta rows, skipping even the
        # summary build.  n_scanned keeps the reach count (identical to the
        # unskipped fold's accounting).
        alo = getattr(snap, "attr_lo", None)
        ahi = getattr(snap, "attr_hi", None)
        if alo is not None and ahi is not None:
            lo = np.asarray(plan.lo_pad)
            hi = np.asarray(plan.hi_pad)
            nonvoid = np.all(lo <= hi, axis=-1)  # [Qpad, F]
            overlap = np.all(
                (lo <= ahi[None, None, :]) & (hi >= alo[None, None, :]),
                axis=-1,
            )
            if not bool(np.any(nonvoid & overlap)):
                self.stats.delta_skips += 1
                self.stats.delta_interval_skips += 1
                dscan = delta_lib.snapshot_reach(
                    snap, plan.geo_probes, plan.geo_valid
                )
                q = plan.q
                return dataclasses.replace(
                    res, n_scanned=res.n_scanned + dscan[:q]
                )

        # Delta-tier scan skip: a tiny resident interval/histogram summary
        # over the segment's live rows (same machinery as the cluster
        # summaries, same soundness contract) proves when a batch's filters
        # can match zero delta rows — then the whole [Qpad, C] scan and its
        # top-k merge are provably all-masked no-ops.  Only the cheap
        # reach count survives, so n_scanned stays bit-identical to the
        # unskipped fold.
        summ = delta_lib.snapshot_summary(snap)
        if summ is None or not bool(np.asarray(
                summaries_lib.can_match(summ, plan.lo_pad, plan.hi_pad)
        ).any()):
            self.stats.delta_skips += 1
            if summ is None:  # no live rows: reach is identically zero
                return res
            dscan = delta_lib.snapshot_reach(
                snap, plan.geo_probes, plan.geo_valid
            )
            q = plan.q
            return dataclasses.replace(
                res, n_scanned=res.n_scanned + dscan[:q]
            )

        dvals, dids, dscan, dpass = delta_lib.scan_snapshot(
            snap, plan.queries, plan.queries_pad, plan.lo_pad, plan.hi_pad,
            plan.geo_probes, plan.geo_valid,
            metric=self.index.spec.metric, k=self.k,
        )
        q = plan.q
        vals, out_ids = topk_lib.merge_topk(
            (res.scores, res.ids), (dvals[:q], dids[:q]), self.k
        )
        self.stats.delta_folds += 1
        return dataclasses.replace(
            res, scores=vals, ids=out_ids,
            n_scanned=res.n_scanned + dscan[:q],
            n_passed=res.n_passed + dpass[:q],
        )

    def scan_merge(self, plan: SearchPlan, operands) -> SearchResult:
        """Whole-batch scan/merge over fetched operands (sync executor)."""
        # times the dispatch: the scan is still running on the device
        with self._stage("scan_dispatch"):
            slot_cluster, vectors, attrs, ids, norms, scales = operands
            ids = self._mask_tombstones(plan, ids)
            metric = self.index.spec.metric
            self._count_scan(self._scan_key(
                plan, q=plan.q, qpad=plan.n_tiles * plan.q_block,
                s=plan.n_tiles * plan.u_cap, q_block=plan.q_block,
                vectors=vectors, norms=norms, scales=scales,
            ))
            res, fold = _scan_merge_tiled(
                jnp.asarray(slot_cluster), jnp.asarray(plan.slot_tile),
                jnp.asarray(plan.slot_of_probe), jnp.asarray(plan.probe_ok),
                plan.queries, plan.queries_pad, plan.lo_pad, plan.hi_pad,
                vectors, attrs, ids, norms, scales,
                metric=metric, k=self.k, q=plan.q, q_block=plan.q_block,
                v_block=self.v_block, backend=self.backend,
            )
            self._note_fold(fold, plan.n_tiles * plan.u_cap, vectors)
        return dataclasses.replace(res, n_pruned=plan.n_pruned)

    def _scan_tile(self, plan: SearchPlan, i: int, operands) -> SearchResult:
        """Scan/merge one query tile (pipelined executor).  Same jitted
        stage as the monolith with ``n_tiles=1`` — per-slot arithmetic is
        identical, so tile results concatenate to the sync result bitwise."""
        with self._stage("scan_dispatch"):
            slot_cluster, vectors, attrs, ids, norms, scales = operands
            ids = self._mask_tombstones(plan, ids)
            qb, cap = plan.q_block, plan.u_cap
            metric = self.index.spec.metric
            if plan.queries_orig_pad is None:  # plan built for a sync run
                plan.queries_orig_pad = probes_lib.pad_to_tiles(
                    plan.queries, qb)
            rows = slice(i * qb, (i + 1) * qb)
            # tile-local slot pointers
            sop = plan.slot_of_probe[rows] - i * cap
            self._count_scan(self._scan_key(
                plan, q=qb, qpad=qb, s=cap, q_block=qb,
                vectors=vectors, norms=norms, scales=scales,
            ))
            res, fold = _scan_merge_tiled(
                jnp.asarray(slot_cluster),
                jnp.zeros((cap,), jnp.int32),
                jnp.asarray(sop), jnp.asarray(plan.probe_ok[rows]),
                plan.queries_orig_pad[rows], plan.queries_pad[rows],
                plan.lo_pad[rows], plan.hi_pad[rows],
                vectors, attrs, ids, norms, scales,
                metric=metric, k=self.k, q=qb, q_block=qb,
                v_block=self.v_block, backend=self.backend,
            )
            self._note_fold(fold, cap, vectors)
        return res

    def _fetch_segment(self, plan: SearchPlan, seg_sc: np.ndarray,
                       alive_seg: np.ndarray, ops: Dict[int, dict]):
        """Per-segment lazy fetch for the sharded terminated executor.

        Clusters first needed by this segment whose every (query, probe)
        pair is already dead at the boundary are dropped from the per-owner
        fetch list before dispatch (the store counts ``fetches_skipped``)
        and scanned as all-masked zero blocks — every candidate they might
        have held is provably below the final kth, so results stay exact
        while the ring never sees the fetch.  Live records are kept in the
        batch-scoped ``ops`` cache; skipped clusters are NOT cached, so a
        later tile where they are alive fetches them for real."""
        spec = self._bspec
        uniq, local = blockstore_lib.first_need_unique(seg_sc)
        slot_alive = alive_seg.any(axis=0)  # [seg]
        cid_alive = np.zeros(len(uniq), bool)
        np.logical_or.at(cid_alive, local, slot_alive)
        need = np.asarray(
            [j for j, c in enumerate(uniq) if int(c) not in ops], np.int64
        )
        if need.size:
            need_ids = uniq[need]
            recs = self._store.get(
                need_ids,
                gens=(plan.gens[need_ids] if plan.gens is not None
                      else None),
                alive=cid_alive[need],
            )
            self._count_fetched(plan, recs)
            for c, r in recs.items():
                ops[int(c)] = r
        dead = None
        view = {}
        for c in uniq:
            r = ops.get(int(c))
            if r is None:  # skipped this segment: all-masked zero block
                if dead is None:
                    dead = blockstore_lib.dead_record(spec)
                r = dead
            view[int(c)] = r
        # pad the unique list to the fixed segment width so segment scans
        # share one operand shape per (bucket, record vpad)
        seg_w = int(seg_sc.shape[0])
        if len(uniq) < seg_w:
            uniq = np.concatenate(
                [uniq, np.repeat(uniq[-1:], seg_w - len(uniq))]
            )
        return blockstore_lib.assemble_blocks(seg_sc, uniq, local, view,
                                              spec, as_device=True)

    def _scan_tile_terminated(self, plan: SearchPlan, i: int,
                              operands, ops: Optional[Dict[int, dict]] = None
                              ) -> SearchResult:
        """Bound-driven scan of one query tile: best-bound-first segments,
        running top-k folded after each, remaining (query, slot) pairs
        dropped when their score upper bound provably (or, in ε mode,
        probably) cannot reach the query's top-k.

        Exactness: a pair dropped under the provable rule scores strictly
        below the query's *running* kth, which only rises — so it is
        strictly below the final kth and its fragments could never surface
        in the merged top-k.  Pairs whose segment WAS scanned (for another
        query) keep their fragments in the merge, so ``termination="exact"``
        reproduces the unterminated scan bitwise.  ε-dropped pairs are
        always masked — the result is the exact top-k over the surviving
        probe set, which shrinks monotonically with ε.

        ``operands=None`` runs the *segmented-fetch* mode (sharded ring):
        each segment's clusters are fetched right before its scan through
        :meth:`_fetch_segment`, so boundary drops shrink the remote fetch
        lists; ``ops`` is the batch-scoped record cache.
        """
        # each segment boundary reads the running top-k back to the host,
        # so this span times the scan on the device, not only its dispatch
        with self._stage("scan"):
            return self._scan_segments(plan, i, operands, ops)

    def _scan_segments(self, plan: SearchPlan, i: int, operands,
                       ops: Optional[Dict[int, dict]]) -> SearchResult:
        from repro.kernels.filtered_scan.filtered_scan import (
            fold_running_topk,
        )

        term = plan.term
        qb, cap, k = plan.q_block, plan.u_cap, self.k
        seg, n_seg = term.seg, term.n_seg
        cap_pad = n_seg * seg
        metric = self.index.spec.metric
        if plan.queries_orig_pad is None:
            plan.queries_orig_pad = probes_lib.pad_to_tiles(plan.queries, qb)
        rows = slice(i * qb, (i + 1) * qb)
        sop = np.asarray(plan.slot_of_probe[rows]) - i * cap
        pok = np.asarray(plan.probe_ok[rows])
        q_pad = plan.queries_pad[rows]
        lo_pad = plan.lo_pad[rows]
        hi_pad = plan.hi_pad[rows]
        segmented = operands is None
        if segmented:
            sc = np.asarray(plan.slot_cluster).reshape(
                plan.n_tiles, cap
            )[i].astype(np.int64)
            vectors = attrs = ids = norms = scales = None
            live_np = None  # filled per scanned segment
        else:
            slot_cluster, vectors, attrs, ids, norms, scales = operands
            ids = self._mask_tombstones(plan, ids)
            sc = np.asarray(slot_cluster).reshape(-1)
        # pad the tile's slot list to the segmented width with the standard
        # repeat-last-slot convention (scanned only if its segment is)
        if cap_pad > cap:
            sc = np.concatenate([sc, np.repeat(sc[-1:], cap_pad - cap)])
        if segmented:
            live_np = np.zeros((cap_pad,), np.int32)
            live_per_slot = None
            sc_dev = None
        else:
            sc_dev = jnp.asarray(sc, jnp.int32)
            live_per_row = jnp.sum((ids >= 0).astype(jnp.int32), axis=-1)
            live_per_slot = jnp.take(live_per_row, sc_dev)

        alive = term.valid[i].copy()              # [qb, cap_pad]
        eps_dropped = np.zeros((qb, cap_pad), bool)
        scanned = np.zeros((n_seg,), bool)
        run_vals = jnp.full((qb, k), topk_lib.NEG_INF, jnp.float32)
        run_ids = jnp.full((qb, k), -1, jnp.int32)
        frags: List[Optional[Tuple]] = []
        for si in range(n_seg):
            p0, p1 = si * seg, (si + 1) * seg
            alive_seg = alive[:, p0:p1]
            if not alive_seg.any():
                self.stats.term_segments_skipped += 1
                frags.append(None)
            else:
                scanned[si] = True
                if segmented:
                    with self._stage("fetch"):
                        (seg_local, vectors, attrs, ids, norms,
                         scales) = self._fetch_segment(
                            plan, sc[p0:p1], alive_seg, ops
                        )
                    ids = self._mask_tombstones(plan, ids)
                    live_row = np.asarray(
                        jnp.sum((ids >= 0).astype(jnp.int32), axis=-1)
                    )
                    seg_local = np.asarray(seg_local)
                    live_np[p0:p1] = live_row[seg_local]
                    scan_sc = jnp.asarray(seg_local, jnp.int32)
                else:
                    scan_sc = sc_dev[p0:p1]
                self._count_scan((
                    "term", self.backend, metric, k, qb, self.v_block, seg,
                    np.shape(vectors), str(vectors.dtype),
                    str(q_pad.dtype), tuple(lo_pad.shape[1:]),
                    norms is None, scales is None,
                ))
                svals, sids, snpass, fold = _scan_slots(
                    scan_sc, q_pad, lo_pad, hi_pad,
                    vectors, attrs, ids, norms, scales,
                    metric=metric, k=k, q_block=qb, v_block=self.v_block,
                    backend=self.backend,
                )
                self._note_fold(fold, seg, vectors)
                frags.append((svals, sids, snpass))
                run_vals, run_ids = fold_running_topk(
                    run_vals, run_ids, svals, sids, jnp.asarray(alive_seg),
                    k=k,
                )
            if si + 1 >= n_seg:
                break
            # boundary: compare remaining pairs' upper bounds against the
            # running kth (one host sync per boundary, n_seg − 1 per tile)
            kth = np.asarray(run_vals)[:, k - 1]
            kth_real = kth > topk_lib.NEG_INF / 2
            rest = np.s_[:, p1:]
            drop = (alive[rest] & kth_real[:, None]
                    & (term.ub[i][rest] < kth[:, None]))
            if si == 0 and term.epsilon > 0.0:
                # the ε decision is made exactly once, at the first
                # boundary, from an ε-independent kth — so higher ε drops a
                # superset of lower ε's pairs and recall is monotone in ε
                ub_r, lb_r = term.ub[i][rest], term.lb[i][rest]
                m_r = term.mass[i][rest]
                p_hit = np.clip(
                    (ub_r - kth[:, None])
                    / np.maximum(ub_r - lb_r, 1e-12),
                    0.0, 1.0,
                )
                p_hit = np.where(kth_real[:, None], p_hit, 1.0)
                p_any = 1.0 - np.power(
                    1.0 - np.minimum(p_hit, 1.0 - 1e-12), m_r
                )
                edrop = alive[rest] & (p_any <= term.epsilon)
                eps_dropped[rest] |= edrop
                drop = drop | edrop
            self.stats.probes_terminated += int(drop.sum())
            alive[rest] &= ~drop
        # never-scanned segments contribute all-masked filler fragments so
        # the merge sees one fixed [cap_pad, QB, k] shape per bucket
        filler = None
        for si in range(n_seg):
            if frags[si] is None:
                if filler is None:
                    filler = (
                        jnp.full((seg, qb, k), topk_lib.NEG_INF,
                                 jnp.float32),
                        jnp.full((seg, qb, k), -1, jnp.int32),
                        jnp.zeros((seg, qb), jnp.int32),
                    )
                frags[si] = filler
        svals_all = jnp.concatenate([f[0] for f in frags], axis=0)
        sids_all = jnp.concatenate([f[1] for f in frags], axis=0)
        snpass_all = jnp.concatenate([f[2] for f in frags], axis=0)
        # a probe's fragments enter the merge iff its segment was scanned
        # and it was not ε-dropped; provably-dropped pairs of a scanned
        # segment stay in (their rows are strictly below the final kth —
        # keeping them preserves bitwise identity with the full scan)
        scanned_pos = np.repeat(scanned, seg)
        qi = np.broadcast_to(np.arange(qb)[:, None], sop.shape)
        scan_ok = pok & scanned_pos[sop]
        pair_ok = scan_ok & ~eps_dropped[qi, sop]
        if segmented:
            live_per_slot = jnp.asarray(live_np)
        res = _merge_tile_fragments(
            svals_all, sids_all, snpass_all, jnp.asarray(sop),
            jnp.asarray(pair_ok), jnp.asarray(scan_ok),
            plan.queries_orig_pad[rows], live_per_slot,
            metric=metric, k=k, q=qb,
        )
        return res

    def _execute_terminated_sync(self, plan: SearchPlan) -> SearchResult:
        """Sync executor, termination active: one whole-batch fetch, then
        per-tile segmented scans (the early-termination decisions need the
        per-tile running kth, so the monolithic all-tiles scan is replaced
        by a loop over the same compiled per-segment stage)."""
        if (self._store is not None and self._device_cache is None
                and isinstance(self._store,
                               blockstore_lib.ShardedBlockStore)):
            return self._execute_terminated_segmented(plan)
        operands = self.fetch(plan)
        slot_cluster = np.asarray(operands[0]).reshape(
            plan.n_tiles, plan.u_cap
        )
        parts: List[SearchResult] = []
        for i in range(plan.n_tiles):
            parts.append(self._scan_tile_terminated(
                plan, i, (slot_cluster[i],) + tuple(operands[1:])
            ))
            self.stats.tiles_scanned += 1
        return self._merge_parts(plan, parts)

    def _execute_terminated_segmented(self, plan: SearchPlan
                                      ) -> SearchResult:
        """Terminated executor over a sharded ring: per-segment lazy fetch
        instead of one whole-batch gather, so a cluster every query has
        already dropped at a segment boundary is never dispatched to its
        owning peer (the sharded-ring fetch shrink;
        ``StoreStats.fetches_skipped``).  Scores/ids stay exact — a skipped
        cluster's candidates are all provably below the final kth —
        while ``n_scanned`` counts only actually-fetched rows."""
        ops: Dict[int, dict] = {}
        parts: List[SearchResult] = []
        for i in range(plan.n_tiles):
            parts.append(self._scan_tile_terminated(plan, i, None, ops=ops))
            self.stats.tiles_scanned += 1
        return self._merge_parts(plan, parts)

    def _note_partition_rows(self, plan: SearchPlan, res: SearchResult):
        """Splits the batch's cold-scan row accounting by routing outcome
        (partition vs flat path) — the partition plane's effectiveness
        gauge.  No-op (and no host sync) without an active catalog."""
        if plan.route is None:
            return
        ns = np.asarray(res.n_scanned)
        hit = plan.route >= 0
        self.stats.partition_rows_scanned += int(ns[hit].sum())
        self.stats.flat_rows_scanned += int(ns[~hit].sum())

    # ---- executors ----
    def execute(self, plan: SearchPlan) -> SearchResult:
        self.stats.batches += 1
        if self.pipeline == "on":
            res = self._execute_pipelined(plan)
        elif plan.term is not None:
            res = self._execute_terminated_sync(plan)
        else:
            res = self.scan_merge(plan, self.fetch(plan))
        self._note_partition_rows(plan, res)
        res = self._fold_delta(plan, res)
        self._note_degraded()
        return res

    def _note_degraded(self):
        """Counts batches served while the fetch store was routing around
        an unhealthy peer (failover keeps results bit-identical, so this
        counter is the only visible trace)."""
        if self._store is not None and getattr(self._store, "degraded",
                                               False):
            self.stats.degraded_batches += 1

    # ---- cross-batch software pipeline ----
    def submit(self, queries: Array, fspec: FilterSpec) -> "PendingSearch":
        """Starts a batch: plans it and (pipelined, disk tier) launches its
        first ``pipeline_depth`` tile gathers immediately.

        With :meth:`result` this software-pipelines *across batches*: submit
        batch *i+1* while batch *i* scans, and batch *i+1*'s clusters page
        in + transfer behind batch *i*'s compute.  At serving batch sizes
        of one tile (``Q ≤ q_block``) this is the only place IO/compute
        overlap can come from — within-batch double buffering needs ≥ 2
        tiles.  Multi-tile batches pipeline best with ``pipeline_depth ≥
        n_tiles`` when batches are interleaved through submit/result (the
        single fetch worker serves gathers strictly in submission order).
        """
        plan = self.plan(queries, fspec)
        self.stats.batches += 1
        if self.pipeline != "on" or self._gather_fn is None:
            return PendingSearch(plan=plan, inflight=None)
        depth = min(self.pipeline_depth, plan.n_tiles)
        inflight = self._start_inflight(plan, depth)
        return PendingSearch(plan=plan, inflight=inflight)

    def result(self, pending: "PendingSearch") -> SearchResult:
        """Finishes a :meth:`submit`-started batch (scan + merge)."""
        plan = pending.plan
        if pending.inflight is None:
            if self.pipeline == "on":
                res = self._execute_pipelined(plan)
            elif plan.term is not None:
                res = self._execute_terminated_sync(plan)
            else:
                res = self.scan_merge(plan, self.fetch(plan))
        else:
            res = self._run_tiles(plan, pending.inflight)
        self._note_partition_rows(plan, res)
        res = self._fold_delta(plan, res)
        self._note_degraded()
        return res

    def _tile_operands(self, plan: SearchPlan, i: int):
        """RAM-tier per-tile operands: resident arrays + the tile's global
        slot ids (no fetch needed)."""
        index = self.index
        sc = plan.slot_cluster.reshape(plan.n_tiles, plan.u_cap)[i]
        return (sc, index.vectors, index.attrs, index.ids, index.norms,
                index.scales)

    def _ensure_pool(self):
        """The engine's single fetch/assembly worker: tasks run strictly in
        submission order, keeping per-tile waits aligned with submits."""
        from concurrent.futures import ThreadPoolExecutor

        if getattr(self, "_pool", None) is None:
            self._pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="engine-fetch"
            )
        return self._pool

    def _start_inflight(self, plan: SearchPlan, depth: int) -> Dict:
        """Prepares a pipelined batch (operand cache + per-tile novel fetch
        lists when the BlockStore path is active) and launches the first
        ``depth`` tile fetches."""
        if self._device_cache is not None:
            # the device cache subsumes the per-batch operand cache: the
            # per-tile novel lists still bound what crosses the store, but
            # in-batch reuse rides the same cross-batch device entries
            plan.tile_work()
        elif self._use_operand_cache:
            plan.operands = {}
            plan.tile_work()  # per-tile novel-cluster lists (host tables)
        return {i: self._submit(plan, i) for i in range(depth)}

    def _assemble_tile(self, plan: SearchPlan, i: int, h_store):
        """Engine-worker half of the BlockStore fetch: wait the store's
        records, merge them into the batch operand cache (when enabled),
        assemble tile *i*'s ``[u_cap, ...]`` blocks and move them on-device
        — all off the scan thread, so both IO (store worker) and assembly +
        host→device copy (this worker) hide behind the previous tile's
        scan.  With the operand cache, a cluster several tiles share is
        fetched through the store once per batch; later tiles assemble it
        straight from the batch-local records (``blocks_reused``)."""
        recs = self._store.wait(h_store)
        if self._device_cache is not None or plan.operands is not None:
            self._count_fetched(plan, recs)
        else:
            self.stats.blocks_fetched += len(recs)
        sc = plan.slot_cluster.reshape(plan.n_tiles, plan.u_cap)[i]
        uniq, local = blockstore_lib.first_need_unique(sc)
        if self._device_cache is not None:
            return self._assemble_tile_device(plan, uniq, local, recs,
                                              sc.shape[0])
        if plan.operands is not None:  # per-batch reuse on
            # the operand cache keys on (cluster_id, gen) like every other
            # cache layer — plan.gens is fixed for the batch, so this is a
            # pure re-keying, but it keeps the invalidation contract uniform
            gens = plan.gens

            def gkey(c):
                cid = int(c)
                return (cid, int(gens[cid]) if gens is not None else 0)

            ops = plan.operands
            for c, r in recs.items():
                ops[gkey(c)] = r
            # fetch lists and slot tables always agree; tolerate a gap by
            # fetching inline rather than scanning stale rows
            missing = [int(c) for c in uniq if gkey(c) not in ops]
            if missing:
                more = self._store.get(
                    np.asarray(missing, np.int64),
                    gens=self._expected_gens(plan, missing),
                )
                self._count_fetched(plan, more)
                for c, r in more.items():
                    ops[gkey(c)] = r
            self.stats.blocks_reused += max(
                len(uniq) - len(recs) - len(missing), 0
            )
            view = {int(c): ops[gkey(c)] for c in uniq}
            out = blockstore_lib.assemble_blocks(sc, uniq, local, view,
                                                 self._bspec, as_device=True)
            # free records whose last consuming tile is this one: the
            # batch cache's footprint tracks live overlap ranges, not the
            # batch's whole unique set — an evicted-under-budget record
            # must not be kept alive past its last use (a later surprise
            # consumer re-fetches via the `missing` fallback above)
            if plan.tiles is not None:
                for c in plan.tiles[i].release:
                    ops.pop(gkey(c), None)
            return out
        return blockstore_lib.assemble_blocks(sc, uniq, local, recs,
                                              self._bspec, as_device=True)

    def _assemble_tile_device(self, plan: SearchPlan, uniq, local, recs,
                              s: int):
        """Device-cache half of :meth:`_assemble_tile`: the tile's blocks
        are composed from resident device entries (cross-batch hits) plus
        this tile's store fetches, which are device-put once and admitted
        — so a cluster several tiles (or batches) share never re-crosses
        the store, the host assembler, or the H2D bus.  A resident entry
        evicted between submit and assembly is re-fetched inline (same
        fallback the operand cache uses), never scanned stale."""
        dc = self._device_cache
        egens = self._expected_gens(plan, uniq)
        tile = dc.get_tile(uniq, s, egens)
        if tile is not None:  # exact repeat: the composed blocks, verbatim
            self._note_device_hits(len(uniq))
            self.stats.blocks_reused += len(uniq)
            dc.put_records(recs)  # admit this tile's fetches regardless
            return (local.astype(np.int32),) + tile
        hits, missing = dc.get_many(uniq, egens)
        self._note_device_hits(len(hits))
        self.stats.blocks_reused += len(hits)
        entries = dict(hits)
        entries.update(dc.put_records(recs))
        gap = [c for c in missing if c not in entries]
        if gap:
            more = self._store.get(
                np.asarray(gap, np.int64),
                gens=self._expected_gens(plan, gap),
            )
            self._count_fetched(plan, more)
            entries.update(dc.put_records(more))
        ordered = [entries[int(c)] for c in uniq]
        blocks = dc.compose(ordered, s)
        dc.put_tile(uniq, s, ordered, blocks)
        return (local.astype(np.int32),) + blocks

    def _submit(self, plan: SearchPlan, i: int):
        """Starts tile *i*'s fetch; returns (handle, t_submit, done_box).
        The waited handle always yields assembled, device-resident
        ``(local_ids, vectors, attrs, ids, norms, scales)`` operands."""
        t0 = time.monotonic()
        done = [None]  # completion timestamp, set by the done-callback
        if self._store is not None:
            if self._device_cache is not None:
                # fetch only this tile's novel clusters that are not already
                # device-resident — on a device hit the store worker never
                # sees the cluster (no disk read, no peer RPC); an entry
                # evicted before assembly is re-fetched inline there
                novel = plan.tile_work()[i].fetch
                fetch_ids = self._device_cache.filter_missing(
                    novel, self._expected_gens(plan, novel)
                )
            elif self._use_operand_cache:
                # fetch only clusters no earlier tile of this batch needed;
                # everything else is already (or will be) in plan.operands
                fetch_ids = plan.tile_work()[i].fetch
            else:
                sc = plan.slot_cluster.reshape(plan.n_tiles, plan.u_cap)[i]
                fetch_ids, _ = blockstore_lib.first_need_unique(sc)
            h_store = self._store.submit(
                fetch_ids, gens=self._expected_gens(plan, fetch_ids)
            )  # IO on the store worker
            h = self._ensure_pool().submit(self._assemble_tile, plan, i,
                                           h_store)
        elif self._async_src is not None:
            sc = plan.slot_cluster.reshape(plan.n_tiles, plan.u_cap)[i]
            h = self._async_src.gather_submit(sc)
        else:
            # generic sync gather_fn: run it on the engine's own worker so
            # the pipeline still overlaps IO with the device scan
            sc = plan.slot_cluster.reshape(plan.n_tiles, plan.u_cap)[i]
            h = self._ensure_pool().submit(self._gather_fn, sc)
        h.add_done_callback(lambda _: done.__setitem__(0, time.monotonic()))
        return h, t0, done

    def _wait(self, handle_rec):
        handle, t_submit, done = handle_rec
        t0 = time.monotonic()
        if self._async_src is not None:
            out = self._async_src.gather_wait(handle)
        else:
            out = handle.result()
        t1 = time.monotonic()
        self.stats.io_wait_s += t1 - t0
        self._observe_stage("fetch", t1 - t0)
        # submit→completion span; a gather that finished long before this
        # wait counts its true (short) duration, not the time it sat done —
        # the callback timestamp may lag result() by a beat, so fall back
        # to t1 when it hasn't landed yet
        t_done = done[0] if done[0] is not None else t1
        self.stats.io_total_s += max(t_done - t_submit, 0.0)
        slot_cluster, vectors, attrs, ids, norms, scales = out
        return (jnp.asarray(slot_cluster), vectors, attrs, ids, norms,
                scales)

    def _execute_pipelined(self, plan: SearchPlan) -> SearchResult:
        """Double-buffered executor: scan tile *i* while tiles
        *i+1 … i+depth* gather in the background.  RAM tier degenerates to
        per-tile scans over the resident arrays (same results, no fetch).

        A serially-executed single-tile batch has nothing to overlap with —
        the pipelined path would only add a thread hop — so it falls back
        to the sync fetch+scan (identical results, sync latency).  Cross-
        batch overlap for single-tile batches comes from
        :meth:`submit`/:meth:`result`, whose gathers are already in flight
        when the result is drained.
        """
        if plan.n_tiles < 2 and self._gather_fn is not None:
            if plan.term is not None:
                return self._execute_terminated_sync(plan)
            return self.scan_merge(plan, self.fetch(plan))
        scan = (self._scan_tile_terminated if plan.term is not None
                else self._scan_tile)
        if self._gather_fn is None:
            self.stats.pipelined_batches += 1
            parts: List[SearchResult] = []
            for i in range(plan.n_tiles):
                parts.append(
                    scan(plan, i, self._tile_operands(plan, i))
                )
                self.stats.tiles_scanned += 1
            return self._merge_parts(plan, parts)
        depth = min(self.pipeline_depth, plan.n_tiles)
        inflight = self._start_inflight(plan, depth)
        return self._run_tiles(plan, inflight)

    def _run_tiles(self, plan: SearchPlan, inflight: Dict) -> SearchResult:
        """Drains a pipelined batch: wait tile i's fetch, keep ``depth``
        fetches in flight, scan, concatenate.  On any failure the remaining
        in-flight handles are still waited (exceptions swallowed) — every
        submit gets its wait, so no future exception goes unretrieved and
        the cache ends consistent — then the original error propagates."""
        self.stats.pipelined_batches += 1
        n = plan.n_tiles
        depth = max(len(inflight), 1)
        scan = (self._scan_tile_terminated if plan.term is not None
                else self._scan_tile)
        parts: List[SearchResult] = []
        try:
            for i in range(n):
                operands = self._wait(inflight.pop(i))
                if i + depth < n:
                    inflight[i + depth] = self._submit(plan, i + depth)
                parts.append(scan(plan, i, operands))
                self.stats.tiles_scanned += 1
        except BaseException:
            for handle_rec in inflight.values():
                try:
                    handle_rec[0].result()
                except BaseException:
                    pass
            raise
        return self._merge_parts(plan, parts)

    def _merge_parts(self, plan: SearchPlan,
                     parts: List[SearchResult]) -> SearchResult:
        with self._stage("merge_dispatch"):
            if len(parts) == 1:
                res = parts[0]
                res = SearchResult(res.scores[: plan.q], res.ids[: plan.q],
                                   res.n_scanned[: plan.q],
                                   res.n_passed[: plan.q])
            else:
                res = SearchResult(
                    jnp.concatenate([p.scores for p in parts])[: plan.q],
                    jnp.concatenate([p.ids for p in parts])[: plan.q],
                    jnp.concatenate([p.n_scanned for p in parts])[: plan.q],
                    jnp.concatenate([p.n_passed for p in parts])[: plan.q],
                )
        return dataclasses.replace(res, n_pruned=plan.n_pruned)

    # ---- the whole pipeline ----
    def search(self, queries: Array, fspec: FilterSpec) -> SearchResult:
        return self.execute(self.plan(queries, fspec))

    # ---- live-update handshake ----
    def refresh(self) -> bool:
        """Atomically flips the engine to the latest published generation.

        Call strictly *between* batches (SearchServer does this on
        ``request_refresh``): reopens the fetch stores' readers, reloads the
        index's resident state (counts / summaries / gens) and commits any
        pending delta freeze.  Gen-keyed caches need no flush — the next
        batch's fetches carry the new expected generations, so exactly the
        rewritten clusters miss and re-page.  Returns True when a new
        generation was picked up."""
        if self._store is not None:
            store_refresh = getattr(self._store, "refresh", None)
            if store_refresh is not None:
                store_refresh()
        idx_refresh = getattr(self.index, "refresh", None)
        changed = bool(idx_refresh()) if idx_refresh is not None else False
        if self._device_cache is not None:
            # same precision contract as the host caches: the new generation
            # vector names exactly the clusters the republish rewrote, and
            # only their device entries (gen below the new minimum) drop —
            # untouched hot clusters stay resident through the flip
            gens = self._plan_gens()
            if gens is not None:
                self._device_cache.invalidate_below(gens)
        return changed

    # ---- observability ----
    def metrics(self) -> Dict[str, Any]:
        """One flat scrape-able dict: engine + store + cache + health +
        delta-tier counters under stable dotted keys (``engine.batches``,
        ``store.per_node.0.hits``, ``cache.invalidations``,
        ``delta.rows``, ...).  Values are scalars (numbers / bools /
        strings) — ready for a metrics exporter, no nesting to unpack."""
        out: Dict[str, Any] = {}
        self._collect_fold()
        eng = dataclasses.asdict(self.stats)
        eng["overlap_ratio"] = self.stats.overlap_ratio
        eng["pipeline"] = self.pipeline
        eng["backend"] = self.backend
        eng["scan_compile_count"] = scan_compile_count()
        _flatten_metrics(out, "engine", eng)
        if self._store is not None:
            store_stats = getattr(self._store, "stats", None)
            if callable(store_stats):
                _flatten_metrics(out, "store", store_stats())
        cache = getattr(self.index, "cache", None)
        cstats = getattr(cache, "stats", None) if cache is not None else None
        if cstats is not None:
            c = dataclasses.asdict(cstats)
            hit_rate = getattr(cache, "hit_rate", None)
            c["hit_rate"] = hit_rate() if callable(hit_rate) else hit_rate
            _flatten_metrics(out, "cache", c)
        if self._device_cache is not None:
            _flatten_metrics(out, "device_cache", self._device_cache.stats())
        tier = self._delta_tier()
        if tier is not None:
            _flatten_metrics(out, "delta", tier.stats())
        cat = getattr(self.index, "partitions", None)
        if cat is not None:
            _flatten_metrics(out, "partitions", dict(
                entries=cat.n_entries, subs=cat.n_subs,
                catalog_bytes=cat.nbytes(),
            ))
        if self._traffic is not None:
            _flatten_metrics(out, "filter_traffic", self._traffic.stats())
        return out

    def metrics_text(self) -> str:
        """:meth:`metrics` rendered in Prometheus text exposition format,
        plus the per-stage fixed-bucket latency histograms
        (``launch/serve.py --metrics-port`` serves this)."""
        return (render_prometheus(self.metrics())
                + render_stage_histograms(self._stage_hist))

    def close(self):
        pool = getattr(self, "_pool", None)
        if pool is not None:
            pool.shutdown(wait=True)
            self._pool = None


def search_fused_tiled(
    index,
    queries: Array,
    fspec: FilterSpec,
    *,
    k: int,
    n_probes: int,
    q_block: int = 64,
    v_block: int = 256,
    u_cap: Optional[int] = None,
    backend: Optional[str] = None,
    gather_fn=None,
    blockstore=None,
    prune: str = "auto",
    t_max=None,
    pipeline: str = "off",
    pipeline_depth: int = 2,
    adaptive_u_cap: bool = False,
    u_cap_ladder: str = "pow2",
    operand_cache: str = "auto",
    termination: Optional[str] = None,
    epsilon: float = 0.0,
    partitions: str = "auto",
) -> SearchResult:
    """Query-tiled, probe-deduplicated fused search with streaming top-k.

    Thin wrapper over :class:`SearchEngine` kept as the functional entry
    point — same contract as :func:`repro.core.search.search_reference`
    (identical ids/scores modulo tie order).  Defaults reproduce the classic
    synchronous path exactly: ``u_cap=None`` provisions the always-sufficient
    worst case (``min(q_block·W, K)``), ``pipeline="off"`` runs one fetch +
    one scan.  ``pipeline="on"`` double-buffers per-tile fetches against the
    scan; ``adaptive_u_cap=True`` buckets the slot-table width from the
    observed post-prune unique counts.  Long-lived callers (servers, benches)
    should hold a :class:`SearchEngine` instead to keep its stats.

    With ``gather_fn=None`` the scan reads ``index``'s in-RAM
    ``[K, Vpad, ...]`` arrays.  A disk-resident index supplies its cluster
    cache's pager (``index.gather`` is picked up automatically by the
    engine): the hook receives the plan's ``slot_cluster`` fetch list and
    returns ``(local_ids, vectors, attrs, ids, norms, scales)`` batch-local
    blocks, which the same kernel scans for bit-identical results.

    ``prune``: ``"auto"`` (default) consults the index's cluster attribute
    summaries when present and drops probes whose clusters provably contain
    no row passing the query's filter — same ids/scores, fewer slots, fewer
    disk fetches.  ``"on"`` requires summaries, ``"off"`` disables.
    ``t_max`` (static, ≥ n_probes; needs pruning active) widens: pruned
    probes are refilled from the query's next-best unpruned centroids within
    the geometric top-``t_max``, trading bit-identity for recovered recall
    under selective filters (every surfaced hit remains exact).
    """
    eng = SearchEngine(
        index, k=k, n_probes=n_probes, q_block=q_block, v_block=v_block,
        u_cap=u_cap, backend=backend, gather_fn=gather_fn,
        blockstore=blockstore, prune=prune, t_max=t_max, pipeline=pipeline,
        pipeline_depth=pipeline_depth, adaptive_u_cap=adaptive_u_cap,
        u_cap_ladder=u_cap_ladder, operand_cache=operand_cache,
        termination=termination, epsilon=epsilon, partitions=partitions,
    )
    try:
        return eng.search(queries, fspec)
    finally:
        eng.close()
