"""Pod-scale filtered search: probe dispatch + hierarchical merge (DESIGN §4).

Sharding model
--------------
The index's cluster axis is contiguously range-sharded over every mesh axis
(``pod × data × model`` flattened): chip ``s`` of ``S`` owns clusters
``[s·K/S, (s+1)·K/S)``.  Queries, centroids and filters are replicated (the
query batch is KiB-scale; the lists are TB-scale — replicating the small side
makes every chip able to compute the dispatch locally with zero
communication).

A probe (q, t) is owned by exactly one chip.  Dispatch mirrors MoE
token→expert routing: sort probes by owner, rank within owner, scatter into a
static ``[S, P_cap]`` slot table.  ``P_cap`` is the per-chip probe capacity
(E[load] = Q·T/S); overflow is *counted*, not silent — an overflowing dispatch
degrades recall and must be observable (SearchResult.n_scanned carries it).

Per chip: the fused Pallas scan streams each slot's cluster block-by-block
(HBM→VMEM — the paper's "load only the probed lists"), then per-slot top-k →
per-query top-k, then a tree merge over ``model → data → pod``.  Each merge
stage moves only ``[axis, Q, k]`` — the collective term stays orders of
magnitude below the scan term (EXPERIMENTS §Roofline).

Tiled backends (``*_tiled``) additionally deduplicate each chip's probes per
(query tile, local cluster) pair before scanning — see ``core/probes.py`` —
so a popular cluster probed by many queries in the batch is streamed from the
chip's HBM exactly once, and the scan runs the query-tiled kernel
(``[QB, D] @ [D, VB]`` matmuls with in-kernel streaming top-k) instead of
per-probe matvecs over a materialized ``[P_cap, Vpad]`` score matrix.

Straggler mitigation: the merge is an associative monoid, so any chip's
contribution can be dropped (deadline expiry, preemption) and the result
remains a valid, slightly-lower-recall answer.  ``shard_ok`` implements the
drop; serving.py owns the deadline policy.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import ivf as ivf_lib
from repro.core import probes as probes_lib
from repro.core import summaries as summaries_lib
from repro.core import topk as topk_lib
from repro.core.filters import FilterSpec
from repro.core.ivf import IVFFlatIndex
from repro.core.search import SearchResult
from repro.kernels.centroid_topk.ops import probe_centroids
from repro.kernels.filtered_scan.filtered_scan import (
    filtered_scan,
    filtered_scan_tiled,
)
from repro.core.engine import tiled_scan_xla

TILED_BACKENDS = ("pallas_tiled", "pallas_tiled_interpret", "xla_tiled")

Array = jax.Array
NEG_INF = topk_lib.NEG_INF


def probe_capacity(q: int, t: int, n_shards: int, slack: float = 2.0) -> int:
    """Static P_cap: expected load × slack, multiple of 8, at least 8."""
    expect = (q * t + n_shards - 1) // n_shards
    cap = int(expect * slack) + 1
    return max(8, ((cap + 7) // 8) * 8)


def dispatch_probes(
    probe_ids: Array, *, n_shards: int, k_local: int, p_cap: int,
    probe_valid: Optional[Array] = None,
    ownership=None,
) -> Tuple[Array, Array, Array, Array]:
    """Builds the probe slot table (replicated computation).

    Args:
      probe_ids: [Q, T] global cluster ids.
      n_shards: S, total chips holding index shards.
      k_local: clusters per shard (K/S, contiguous ranges).
      p_cap: static per-shard slot capacity.
      probe_valid: optional [Q, T] bool — probes the filter-aware planner
        pruned (the cluster's attribute summaries prove no row can pass the
        query's filter).  Pruned probes are dispatched to a sentinel owner
        past every shard: they consume no P_cap slot on any chip, are never
        scanned, and never count toward overflow — the pod-scale analogue of
        the single-host plan dropping them before the per-tile dedup.
      ownership: optional owner/local map with jnp-compatible ``owner_of``/
        ``local_of`` (default: ``blockstore.RangeOwnership(n_shards,
        k_local)``, the contiguous range map).  The SAME object can be
        handed to a :class:`repro.core.blockstore.ShardedBlockStore` so
        shard routing and cache routing agree — a chip's probes always land
        on its own pod's cache (``make_sharded_search`` exposes it in its
        info dict).

    Returns:
      slot_cluster [S, P_cap] int32 — local cluster id per slot (0 for pads),
      slot_query   [S, P_cap] int32 — query row per slot (0 for pads),
      slot_valid   [S, P_cap] bool,
      n_overflowed scalar int32 — live probes dropped by capacity.
    """
    from repro.core.blockstore import RangeOwnership

    if ownership is None:
        ownership = RangeOwnership(n_shards, k_local)
    q, t = probe_ids.shape
    flat = probe_ids.reshape(-1)  # [Q*T]
    owner = ownership.owner_of(flat)
    local = ownership.local_of(flat)
    query = jnp.repeat(jnp.arange(q, dtype=jnp.int32), t)
    if probe_valid is not None:
        # sentinel owner sorts after every real shard; its scatter rows are
        # out of range and dropped, so pruned probes vanish from the table
        owner = jnp.where(probe_valid.reshape(-1), owner, n_shards)

    order = jnp.argsort(owner)
    owner_s = jnp.take(owner, order)
    starts = jnp.searchsorted(owner_s, jnp.arange(n_shards), side="left")
    rank = jnp.arange(q * t) - jnp.take(starts, owner_s)

    sc = jnp.zeros((n_shards, p_cap), jnp.int32)
    sq = jnp.zeros((n_shards, p_cap), jnp.int32)
    sv = jnp.zeros((n_shards, p_cap), jnp.bool_)
    sc = sc.at[owner_s, rank].set(
        jnp.take(local, order).astype(jnp.int32), mode="drop"
    )
    sq = sq.at[owner_s, rank].set(
        jnp.take(query, order).astype(jnp.int32), mode="drop"
    )
    sv = sv.at[owner_s, rank].set(True, mode="drop")
    n_overflowed = jnp.sum(
        jnp.logical_and(rank >= p_cap, owner_s < n_shards).astype(jnp.int32)
    )
    return sc, sq, sv, n_overflowed


def dispatch_probes_tiled(
    probe_ids: Array, *, n_shards: int, k_local: int, p_cap: int,
    u_cap: int, q_block: int, probe_valid: Optional[Array] = None,
    ownership=None,
):
    """Probe dispatch + per-shard (query tile, cluster) deduplication.

    Extends :func:`dispatch_probes` with the tiled kernel's slot tables:
    per shard, the valid probes are deduplicated by ``(query_tile,
    local_cluster)`` so a cluster probed by many queries of a tile is
    scanned once on its owner chip.  ``probe_valid`` threads the planner's
    summary prune mask through: pruned probes take no P_cap slot, no unique
    slot, and no scan on any shard (results stay bit-identical — only
    zero-passing-row clusters are ever pruned).

    Returns the four :func:`dispatch_probes` outputs plus:
      u_cluster [S, u_cap] int32 — local cluster per unique slot (pads
                repeat the last unique id → Pallas skips their re-DMA),
      u_tile    [S, u_cap] int32 — query tile per unique slot,
      slot_of   [S, P_cap] int32 — unique-slot index of each probe,
      u_count   [S] int32 — live unique slots per shard.
    """
    sc, sq, sv, n_overflowed = dispatch_probes(
        probe_ids, n_shards=n_shards, k_local=k_local, p_cap=p_cap,
        probe_valid=probe_valid, ownership=ownership,
    )
    tile = sq // q_block
    key = tile * k_local + sc  # [S, P_cap]
    table, slot_of, u_count = probes_lib.dedup_rows(key, sv, u_cap)
    # u_cap = min(p_cap, k_local·n_tiles) can never overflow; clip anyway.
    slot_of = jnp.minimum(slot_of, u_cap - 1)
    u_cluster = table % k_local
    u_tile = table // k_local
    return sc, sq, sv, n_overflowed, u_cluster, u_tile, slot_of, u_count


def _rank_within_query(slot_query: Array, slot_valid: Array, t: int) -> Array:
    """Rank of each slot among the valid slots serving the same query.

    Bounded by T (a query has exactly T probes globally), so the scatter
    destination [Q, T, k] never overflows.
    """
    p = slot_query.shape[0]
    key = jnp.where(slot_valid, slot_query, jnp.int32(2**30))
    order = jnp.argsort(key)
    key_s = jnp.take(key, order)
    first = jnp.searchsorted(key_s, key_s, side="left")
    rank_s = jnp.arange(p) - first
    rank = jnp.zeros((p,), jnp.int32).at[order].set(rank_s.astype(jnp.int32))
    return jnp.minimum(rank, t - 1)


def _scan_slots_xla(
    vectors, attrs, ids, norms, scales, queries, lo, hi, slot_cluster,
    slot_query, *, metric: str, use_vmap: bool,
) -> Array:
    """XLA-native equivalent of the Pallas scan (identical contract).

    Used for the CPU dry-run lowering (Mosaic kernels need a real TPU to
    lower non-interpreted).  ``use_vmap=False`` streams one slot at a time
    (lax.map — bounded [Vpad, D] live gather, the exec variant);
    ``use_vmap=True`` materializes all slots (accurate while-free HLO for
    cost_analysis — the cost variant).
    """
    from repro.kernels.filtered_scan.ref import filtered_scan_ref

    def one(args):
        sc, sq = args
        return filtered_scan_ref(
            sc[None], sq[None], queries, lo, hi, vectors, attrs, ids,
            norms, scales, metric=metric,
        )[0]

    if use_vmap:
        return jax.vmap(lambda sc, sq: one((sc, sq)))(slot_cluster, slot_query)
    return jax.lax.map(one, (slot_cluster, slot_query))


def _local_shard_search(
    vectors: Array,  # [K_local, Vpad, D]
    attrs: Array,
    ids: Array,
    norms: Optional[Array],
    scales: Optional[Array],
    queries: Array,  # [Q, D] replicated
    lo: Array,
    hi: Array,
    slot_cluster: Array,  # [P_cap]
    slot_query: Array,  # [P_cap]
    slot_valid: Array,  # [P_cap] bool (already gated by shard_ok)
    u_cluster: Optional[Array] = None,  # [U] (tiled backends)
    u_tile: Optional[Array] = None,  # [U]
    slot_of: Optional[Array] = None,  # [P_cap] → index into U
    *,
    metric: str,
    k: int,
    t: int,
    q_block: int,
    v_block: int,
    backend: str,
) -> Tuple[Array, Array]:
    """One chip's contribution: fused scan over its slots → per-query top-k."""
    q = queries.shape[0]
    if backend in TILED_BACKENDS:
        # deduped scan → per-slot [QB, k] fragments → per-probe gather
        if backend == "xla_tiled":
            uvals, uids, _ = tiled_scan_xla(
                u_cluster, u_tile, queries, lo, hi, vectors, attrs, ids,
                norms, scales, metric=metric, k=k, q_block=q_block,
            )
        else:
            uvals, uids, _ = filtered_scan_tiled(
                u_cluster, u_tile, queries, lo, hi, vectors, attrs, ids,
                norms, scales, metric=metric, k=k, q_block=q_block,
                v_block=v_block,
                interpret=backend == "pallas_tiled_interpret",
            )
        row = slot_query % q_block  # [P_cap]
        svals = uvals[slot_of, row]  # [P_cap, k]
        sids = uids[slot_of, row]
        svals = jnp.where(slot_valid[:, None], svals, NEG_INF)
        sids = jnp.where(slot_valid[:, None], sids, -1)
    elif backend in ("pallas", "pallas_interpret", "xla_map", "xla_vmap"):
        if backend in ("pallas", "pallas_interpret"):
            scores = filtered_scan(
                slot_cluster, slot_query, queries, lo, hi, vectors, attrs,
                ids, norms, scales, metric=metric, v_block=v_block,
                interpret=backend == "pallas_interpret",
            )  # [P_cap, Vpad]
        else:
            scores = _scan_slots_xla(
                vectors, attrs, ids, norms, scales, queries, lo, hi,
                slot_cluster, slot_query, metric=metric,
                use_vmap=backend == "xla_vmap",
            )
        scores = jnp.where(slot_valid[:, None], scores, NEG_INF)
        slot_ids = jnp.take(ids, slot_cluster, axis=0)  # [P_cap, Vpad]
        svals, sids = topk_lib.masked_topk(
            scores, None, k, ids=slot_ids
        )  # [P,k]
    else:
        raise ValueError(backend)

    rank = _rank_within_query(slot_query, slot_valid, t)
    qvals = jnp.full((q, t, k), NEG_INF, jnp.float32)
    qids = jnp.full((q, t, k), -1, jnp.int32)
    safe_q = jnp.where(slot_valid, slot_query, q)  # pads scatter out of range
    qvals = qvals.at[safe_q, rank].set(svals, mode="drop")
    qids = qids.at[safe_q, rank].set(sids, mode="drop")
    vals, out_ids = topk_lib.masked_topk(
        qvals.reshape(q, t * k), None, k, ids=qids.reshape(q, t * k)
    )
    return vals, out_ids


@dataclasses.dataclass(frozen=True)
class ShardedSearchConfig:
    k: int = 100
    n_probes: int = 7  # paper's T
    p_cap_slack: float = 2.0
    v_block: int = 256
    q_block: int = 128  # centroid-topk tiles
    k_block: int = 512
    scan_q_block: int = 64  # query-tile height QB for the tiled backends
    use_centroid_kernel: bool = False  # XLA path on CPU; kernel on TPU
    # Per-probe scans: "pallas" (TPU), "pallas_interpret" (CPU tests),
    # "xla_map" (dry-run exec variant), "xla_vmap" (dry-run cost variant).
    # Tiled, probe-deduplicated scans with streaming top-k: "pallas_tiled"
    # (TPU), "pallas_tiled_interpret" (CPU tests), "xla_tiled" (fast CPU).
    # None: "pallas_tiled" on a TPU, "pallas_interpret" elsewhere.
    backend: Optional[str] = None
    quantized: bool = False  # SQ8 lists (see ivf.quantize_index)
    # Filter-aware probe pruning from the index's resident cluster attribute
    # summaries (core/summaries.py), replicated like the centroids: "auto"
    # prunes iff the index carries summaries, "on" requires them, "off"
    # disables.  Pruned probes never consume P_cap slots on their owner
    # shard; ids/scores stay bit-identical to the unpruned dispatch.
    prune: str = "auto"


def make_sharded_search(
    mesh: Mesh,
    metric: str,
    *,
    q_total: int,
    n_clusters: int,
    cfg: ShardedSearchConfig,
    axis_names: Optional[Sequence[str]] = None,
):
    """Builds the pod-scale search step for a given mesh.

    Returns ``(search_fn, shardings)``: ``search_fn(index, queries, fspec,
    shard_ok) -> SearchResult`` (jit-compatible), and a dict mapping index
    leaf names to NamedShardings (cluster axis split over all mesh axes).
    """
    axes = tuple(axis_names or mesh.axis_names)
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    if n_clusters % n_shards:
        raise ValueError(
            f"K={n_clusters} must divide over {n_shards} shards; pad K at "
            f"build time (storage.reshard handles this)."
        )
    k_local = n_clusters // n_shards
    on_tpu = jax.default_backend() == "tpu"
    if cfg.backend is None:
        cfg = dataclasses.replace(
            cfg, backend="pallas_tiled" if on_tpu else "pallas_interpret"
        )
    p_cap = probe_capacity(q_total, cfg.n_probes, n_shards, cfg.p_cap_slack)
    merge_axes = tuple(reversed(axes))  # model → data → pod
    needs_norms = metric == "l2"
    tiled = cfg.backend in TILED_BACKENDS
    scan_qb = min(cfg.scan_q_block, ivf_lib.round_up(q_total, 8))
    q_pad_total = ivf_lib.round_up(q_total, scan_qb)
    n_tiles = q_pad_total // scan_qb
    u_cap = max(1, min(p_cap, k_local * n_tiles))

    shard_spec = P(axes)  # leading (cluster) axis split over all mesh axes
    repl = P()

    def _local(vec, att, idl, nrm, scl, ok, sc, sq, sv, uc, ut, uslot,
               queries, lo, hi):
        sid = jnp.int32(0)
        for a in axes:
            sid = sid * mesh.shape[a] + jax.lax.axis_index(a)
        my_sc = jax.lax.dynamic_index_in_dim(sc, sid, keepdims=False)
        my_sq = jax.lax.dynamic_index_in_dim(sq, sid, keepdims=False)
        my_sv = jax.lax.dynamic_index_in_dim(sv, sid, keepdims=False)
        my_uc = jax.lax.dynamic_index_in_dim(uc, sid, keepdims=False)
        my_ut = jax.lax.dynamic_index_in_dim(ut, sid, keepdims=False)
        my_us = jax.lax.dynamic_index_in_dim(uslot, sid, keepdims=False)
        my_sv = jnp.logical_and(my_sv, ok[0])
        vals, out_ids = _local_shard_search(
            vec, att, idl, nrm if needs_norms else None,
            scl if quantized else None, queries, lo, hi,
            my_sc, my_sq, my_sv, my_uc, my_ut, my_us,
            metric=metric, k=cfg.k, t=cfg.n_probes, q_block=scan_qb,
            v_block=cfg.v_block, backend=cfg.backend,
        )
        return topk_lib.topk_tree_merge(vals, out_ids, cfg.k, merge_axes)

    quantized = cfg.quantized
    sharded_local = jax.shard_map(
        _local,
        mesh=mesh,
        in_specs=(shard_spec, shard_spec, shard_spec, shard_spec, shard_spec,
                  shard_spec, repl, repl, repl, repl, repl, repl, repl, repl,
                  repl),
        out_specs=(repl, repl),
        # pallas_call's out_shape carries no varying-mesh-axes annotation;
        # VMA/replication checking cannot see through it, so it is disabled.
        check_vma=False,
    )

    def search_fn(index: IVFFlatIndex, queries: Array, fspec: FilterSpec,
                  shard_ok: Optional[Array] = None) -> SearchResult:
        if shard_ok is None:
            shard_ok = jnp.ones((n_shards,), jnp.bool_)
        # ---- §4.4 step 2: probe centroids (replicated) ----
        _, probe_ids = probe_centroids(
            queries, index.centroids, t=cfg.n_probes,
            q_block=min(cfg.q_block, queries.shape[0]),
            k_block=min(cfg.k_block, n_clusters),
            metric=metric, use_kernel=cfg.use_centroid_kernel,
            interpret=not on_tpu,
        )
        # ---- filter-aware prune mask (replicated, like the plan stage) ----
        from repro.core.engine import resolve_prune

        summ = resolve_prune(index, cfg.prune)
        if summ is not None:
            cm = summaries_lib.can_match(summ, fspec.lo, fspec.hi)  # [Q, K]
            probe_valid = jnp.take_along_axis(cm, probe_ids, axis=1)
        else:
            probe_valid = None
        # ---- dispatch (replicated compute; each chip consumes its row) ----
        if tiled:
            sc, sq, sv, n_drop, uc, ut, uslot, _ = dispatch_probes_tiled(
                probe_ids, n_shards=n_shards, k_local=k_local, p_cap=p_cap,
                u_cap=u_cap, q_block=scan_qb, probe_valid=probe_valid,
            )
            queries_in = probes_lib.pad_to_tiles(queries, scan_qb)
            lo_in = probes_lib.pad_to_tiles(fspec.lo, scan_qb)
            hi_in = probes_lib.pad_to_tiles(fspec.hi, scan_qb)
        else:
            sc, sq, sv, n_drop = dispatch_probes(
                probe_ids, n_shards=n_shards, k_local=k_local, p_cap=p_cap,
                probe_valid=probe_valid,
            )
            uc = jnp.zeros((n_shards, 1), jnp.int32)
            ut = jnp.zeros((n_shards, 1), jnp.int32)
            uslot = jnp.zeros((n_shards, p_cap), jnp.int32)
            queries_in, lo_in, hi_in = queries, fspec.lo, fspec.hi
        norms = index.norms if needs_norms else jnp.zeros(
            (n_clusters, 1), jnp.float32
        )
        scales = index.scales if quantized else jnp.zeros(
            (n_clusters, 1), jnp.float32
        )
        vals, out_ids = sharded_local(
            index.vectors, index.attrs, index.ids, norms, scales, shard_ok,
            sc, sq, sv, uc, ut, uslot, queries_in, lo_in, hi_in,
        )
        q = queries.shape[0]
        vals, out_ids = vals[:q], out_ids[:q]
        if needs_norms:
            q2 = jnp.sum(queries.astype(jnp.float32) ** 2, -1, keepdims=True)
            vals = jnp.where(vals > NEG_INF / 2, vals - q2, vals)
        zero = jnp.zeros((q,), jnp.int32)
        return SearchResult(vals, out_ids, zero + n_drop, zero)

    shardings = {
        "centroids": NamedSharding(mesh, repl),
        "vectors": NamedSharding(mesh, shard_spec),
        "attrs": NamedSharding(mesh, shard_spec),
        "ids": NamedSharding(mesh, shard_spec),
        "norms": NamedSharding(mesh, shard_spec),
        "scales": NamedSharding(mesh, shard_spec),
        "counts": NamedSharding(mesh, shard_spec),
    }
    from repro.core.blockstore import RangeOwnership

    # The dispatch's ownership map, exposed so the serving layer can hand
    # the SAME map to a ShardedBlockStore — cache routing then agrees with
    # shard routing (a chip's probes are always its own pod's cache load).
    return search_fn, shardings, dict(p_cap=p_cap, k_local=k_local,
                                      n_shards=n_shards,
                                      ownership=RangeOwnership(n_shards,
                                                               k_local))
