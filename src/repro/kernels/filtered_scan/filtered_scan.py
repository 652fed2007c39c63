"""Fused filtered IVF scans — the paper's §4.4 steps 3+4 as Pallas kernels.

Two kernel generations live here:

  * :func:`filtered_scan` — the original per-(query, probe) slot kernel.
    Grid ``(P, Vpad // v_block)``; each step is a ``[VB, D] @ [D, 1]``
    matvec, so the MXU runs ~1/128 utilized and a cluster probed by many
    queries is re-streamed HBM→VMEM once per duplicate slot.
  * :func:`filtered_scan_tiled` — the batched successor.  Queries are tiled
    ``q_block`` at a time, probes are deduplicated per tile (see
    ``core/probes.py``), and the grid becomes ``(unique_slots, Vpad //
    v_block)``: each step scores a whole query tile against the streamed
    block in one ``[QB, D] @ [D, VB]`` matmul and folds the masked scores
    into a running per-slot top-k held in the revisited output block — the
    ``[P, Vpad]`` score matrix is never materialized, and peak memory drops
    from ``O(Q·T·Vpad)`` to ``O(slots·QB·k)``.


The paper's measured bottleneck is the *filtering pass* (1.09 s of 1.428 s):
a separate sweep over the probed lists' attribute rows before any distance is
computed.  On TPU we eliminate that pass instead of accelerating it: the
attribute interval test runs in VREGs on the same VMEM-resident block that the
MXU is scoring, so filtering adds zero extra HBM traffic.

The paper's *dynamic memory loading* ("only the probed lists are loaded into
RAM") maps onto scalar-prefetch block indexing: the probe table
``slot_cluster [P]`` is prefetched into SMEM, and the ``index_map`` of the
database operands selects which cluster's block the next grid step DMAs
HBM→VMEM — the same indirection pattern paged attention uses for KV blocks.
Only probed clusters are ever touched; everything else stays cold in HBM,
exactly like the paper's cold lists stay on disk.

Grid: ``(P, Vpad // v_block)`` — probe slots × intra-list blocks.
Operands (scalar prefetch first, per PrefetchScalarGridSpec):
  slot_cluster [P] int32   — cluster id each slot scans   (SMEM)
  slot_query   [P] int32   — query row each slot serves   (SMEM)
  queries  [Q, D]    f32/bf16
  lo, hi   [Q, F, M] int16 — DNF interval bounds per query
  vectors  [K, Vpad, D]    — flat lists (the big operand, block-streamed)
  attrs    [K, Vpad, M] int16
  ids      [K, Vpad] int32 — liveness: id < 0 ⇒ dead/padded slot
Output:
  scores [P, Vpad] f32 — masked to NEG_INF where the filter/liveness fails.

A "l2" variant additionally streams ``norms [K, Vpad] f32`` and emits
``2·q·v − ‖v‖²`` (the per-query −‖q‖² constant is rank-free and added by the
wrapper for score fidelity).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -3.0e38


def _mask_from_attrs(attrs_i32, lo_i32, hi_i32):
    """[V, M] attrs vs [F, M] bounds → [V] bool (OR over F of AND over M)."""
    a = attrs_i32[:, None, :]  # [V, 1, M]
    inside = jnp.logical_and(a >= lo_i32[None], a <= hi_i32[None])  # [V, F, M]
    return jnp.any(jnp.all(inside, axis=-1), axis=-1)  # [V]


def _scan_kernel_dot(
    slot_cluster_ref,  # scalar prefetch (unused in body; drives index_maps)
    slot_query_ref,
    q_ref,  # [1, D]
    lo_ref,  # [1, F, M]
    hi_ref,  # [1, F, M]
    v_ref,  # [1, VB, D]
    a_ref,  # [1, VB, M]
    id_ref,  # [1, VB]
    o_ref,  # [1, VB]
):
    del slot_cluster_ref, slot_query_ref
    q = q_ref[0].astype(jnp.float32)  # [D]
    v = v_ref[0].astype(jnp.float32)  # [VB, D]
    # MXU: [VB, D] @ [D, 1] → [VB, 1]; fp32 accumulation.
    dots = jax.lax.dot_general(
        v, q[:, None], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )[:, 0]
    a = a_ref[0].astype(jnp.int32)  # [VB, M] — int32 compares on the VPU
    fmask = _mask_from_attrs(
        a, lo_ref[0].astype(jnp.int32), hi_ref[0].astype(jnp.int32)
    )
    live = id_ref[0] >= 0
    o_ref[0] = jnp.where(jnp.logical_and(fmask, live), dots, NEG_INF)


def _scan_kernel_dot_q8(
    slot_cluster_ref,
    slot_query_ref,
    q_ref,  # [1, D]
    lo_ref,
    hi_ref,
    v_ref,  # [1, VB, D] int8
    a_ref,
    id_ref,
    s_ref,  # [1, VB] f32 per-vector SQ8 scale
    o_ref,
):
    """SQ8 variant: int8 rows stream from HBM (half the traffic of bf16);
    the dequant is one VPU multiply on the [VB] dot-product column."""
    del slot_cluster_ref, slot_query_ref
    q = q_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)  # int8 → f32 in VREGs
    dots = jax.lax.dot_general(
        v, q[:, None], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )[:, 0] * s_ref[0]
    a = a_ref[0].astype(jnp.int32)
    fmask = _mask_from_attrs(
        a, lo_ref[0].astype(jnp.int32), hi_ref[0].astype(jnp.int32)
    )
    live = id_ref[0] >= 0
    o_ref[0] = jnp.where(jnp.logical_and(fmask, live), dots, NEG_INF)


def _scan_kernel_l2(
    slot_cluster_ref,
    slot_query_ref,
    q_ref,
    lo_ref,
    hi_ref,
    v_ref,
    a_ref,
    id_ref,
    n_ref,  # [1, VB] f32 ‖v‖²
    o_ref,
):
    del slot_cluster_ref, slot_query_ref
    q = q_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    dots = jax.lax.dot_general(
        v, q[:, None], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )[:, 0]
    score = 2.0 * dots - n_ref[0]
    a = a_ref[0].astype(jnp.int32)
    fmask = _mask_from_attrs(
        a, lo_ref[0].astype(jnp.int32), hi_ref[0].astype(jnp.int32)
    )
    live = id_ref[0] >= 0
    o_ref[0] = jnp.where(jnp.logical_and(fmask, live), score, NEG_INF)


@functools.partial(
    jax.jit,
    static_argnames=("v_block", "interpret", "metric"),
)
def filtered_scan(
    slot_cluster: jax.Array,
    slot_query: jax.Array,
    queries: jax.Array,
    lo: jax.Array,
    hi: jax.Array,
    vectors: jax.Array,
    attrs: jax.Array,
    ids: jax.Array,
    norms: Optional[jax.Array] = None,
    scales: Optional[jax.Array] = None,
    *,
    metric: str = "dot",
    v_block: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Runs the fused scan. Returns masked scores [P, Vpad] f32.

    v_block: intra-list block length; VMEM working set per step is
    ``v_block·(D·bytes(core) + M·2 + 8)`` — 256×768 bf16 ≈ 384 KiB, well
    inside the ~16 MiB v5e VMEM budget, leaving room for double buffering.
    """
    p = slot_cluster.shape[0]
    k, vpad, d = vectors.shape
    m = attrs.shape[-1]
    f = lo.shape[1]
    v_block = min(v_block, vpad)
    while vpad % v_block != 0 and v_block > 8:
        v_block //= 2  # builds pad Vpad to ×128, so 128 always divides
    if vpad % v_block != 0:
        raise ValueError(f"vpad={vpad} has no usable v_block ≤ requested")
    if metric not in ("dot", "l2"):
        raise ValueError(metric)
    if metric == "l2" and norms is None:
        raise ValueError("metric='l2' requires norms")

    nvb = vpad // v_block
    grid = (p, nvb)

    # index_maps receive (grid idxs..., *scalar_prefetch_refs)
    def im_query(pi, vi, sc, sq):
        del vi, sc
        return (sq[pi], 0)

    def im_bounds(pi, vi, sc, sq):
        del vi, sc
        return (sq[pi], 0, 0)

    def im_vec(pi, vi, sc, sq):
        del sq
        return (sc[pi], vi, 0)

    def im_rows(pi, vi, sc, sq):
        del sq
        return (sc[pi], vi)

    def im_out(pi, vi, sc, sq):
        del sc, sq
        return (pi, vi)

    in_specs = [
        pl.BlockSpec((1, d), im_query),
        pl.BlockSpec((1, f, m), im_bounds),
        pl.BlockSpec((1, f, m), im_bounds),
        pl.BlockSpec((1, v_block, d), im_vec),
        pl.BlockSpec((1, v_block, m), im_vec),
        pl.BlockSpec((1, v_block), im_rows),
    ]
    operands = [queries, lo, hi, vectors, attrs, ids]
    if metric == "l2":
        if scales is not None:
            raise NotImplementedError("SQ8 + l2 not wired (norms suffice)")
        in_specs.append(pl.BlockSpec((1, v_block), im_rows))
        operands.append(norms)
        kernel = _scan_kernel_l2
    elif scales is not None:
        in_specs.append(pl.BlockSpec((1, v_block), im_rows))
        operands.append(scales)
        kernel = _scan_kernel_dot_q8
    else:
        kernel = _scan_kernel_dot

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, v_block), im_out),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((p, vpad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(slot_cluster.astype(jnp.int32), slot_query.astype(jnp.int32), *operands)
    return out


# ---------------------------------------------------------------------------
# Tiled, probe-deduplicated variant with in-kernel streaming top-k
# ---------------------------------------------------------------------------


def _fold_topk(run_v, run_i, scores, ids_blk, k):
    """Monoid fold: best k of (running set ∪ block), by iterative extraction.

    Branch-free static-k max-extraction (the centroid_topk idiom) — no
    reliance on sort/top_k lowering inside the kernel.  Ties resolve to the
    earliest candidate position, running set first and then the block in
    slot order, which reproduces ``lax.top_k``'s first-index tie order over
    the flat list.

    Shapes: ``run_v/run_i [QB, k]``, ``scores [QB, VB]``, ``ids_blk [1, VB]``.
    The two candidate sets are never concatenated (a lane-unaligned concat
    at k=100), every reduction keeps its lane axis (``[QB, 1]`` columns), and
    the picked id is selected through the one-hot position mask rather than
    an in-kernel gather, so the body lowers to plain VPU/XLU ops in Mosaic.
    """
    big = jnp.int32(2**30)
    col_k = jax.lax.broadcasted_iota(jnp.int32, run_v.shape, 1)
    col_b = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)

    def extract(j, carry):
        run_v, blk_v, out_v, out_i = carry
        m = jnp.maximum(
            jnp.max(run_v, axis=1, keepdims=True),
            jnp.max(blk_v, axis=1, keepdims=True),
        )  # [QB, 1]
        pos_r = jnp.min(jnp.where(run_v == m, col_k, big), axis=1,
                        keepdims=True)
        pos_b = jnp.min(jnp.where(blk_v == m, col_b, big), axis=1,
                        keepdims=True)
        in_run = pos_r < big  # earliest position: the running set wins ties
        hit_r = jnp.logical_and(col_k == pos_r, in_run)
        hit_b = jnp.logical_and(col_b == pos_b, jnp.logical_not(in_run))
        picked = (jnp.sum(jnp.where(hit_r, run_i, 0), axis=1, keepdims=True)
                  + jnp.sum(jnp.where(hit_b, ids_blk, 0), axis=1,
                            keepdims=True))
        out_v = jnp.where(col_k == j, m, out_v)
        out_i = jnp.where(col_k == j, jnp.where(m > NEG_INF / 2, picked, -1),
                          out_i)
        return (jnp.where(hit_r, NEG_INF, run_v),
                jnp.where(hit_b, NEG_INF, blk_v), out_v, out_i)

    _, _, out_v, out_i = jax.lax.fori_loop(
        0, k, extract, (run_v, scores, run_v, run_i)
    )
    return out_v, out_i


def _tiled_kernel(
    slot_cluster_ref,  # scalar prefetch (drives index_maps)
    slot_tile_ref,
    q_ref,  # [QB, D]
    lo_ref,  # [QB, F·M] int32
    hi_ref,  # [QB, F·M] int32
    v_ref,  # [1, VB, D]
    a_ref,  # [1, M, VB] int16
    id_ref,  # [1, 1, VB]
    *rest,  # ([aux_ref [1,1,VB]], ov_ref [1,QB,k], oi_ref [1,QB,k],
    #          op_ref [1,QB,1])
    k: int,
    metric: str,
    quantized: bool,
    n_terms: int,
    n_attrs: int,
):
    del slot_cluster_ref, slot_tile_ref
    if metric == "l2" or quantized:
        aux_ref, ov_ref, oi_ref, op_ref = rest
    else:
        aux_ref = None
        ov_ref, oi_ref, op_ref = rest
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        ov_ref[...] = jnp.full_like(ov_ref, NEG_INF)
        oi_ref[...] = jnp.full_like(oi_ref, -1)
        op_ref[...] = jnp.zeros_like(op_ref)

    q = q_ref[...].astype(jnp.float32)  # [QB, D]
    v = v_ref[0].astype(jnp.float32)  # [VB, D]
    # MXU: one [QB, D] @ [D, VB] matmul scores the whole query tile against
    # the streamed block — compute-dense where the matvec kernel was ~1/QB
    # utilized.  fp32 accumulation.
    scores = jax.lax.dot_general(
        q, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [QB, VB]
    if quantized:
        scores = scores * aux_ref[0]  # SQ8 dequant on the VPU ([1, VB] row)
    if metric == "l2":
        scores = 2.0 * scores - aux_ref[0]  # ‖q‖² added by the wrapper

    # Per-query DNF interval test, built one attribute at a time as
    # [1, VB] row vs [QB, 1] column compares: the rows sit on the lane axis,
    # so no [QB, VB, M] intermediate with M on the lanes ever exists.
    a = a_ref[0].astype(jnp.int32)  # [M, VB]
    lo = lo_ref[...]  # [QB, F·M]
    hi = hi_ref[...]
    fmask = None  # None ⇔ every row passes (no attributes to test)
    for fi in range(n_terms if n_attrs else 0):
        term = None
        for mi in range(n_attrs):
            c = fi * n_attrs + mi
            row = a[mi:mi + 1, :]
            inside = jnp.logical_and(row >= lo[:, c:c + 1],
                                     row <= hi[:, c:c + 1])
            term = inside if term is None else jnp.logical_and(term, inside)
        fmask = term if fmask is None else jnp.logical_or(fmask, term)
    live = id_ref[0] >= 0  # [1, VB]
    mask = live if fmask is None else jnp.logical_and(fmask, live)
    mask = jnp.broadcast_to(mask, scores.shape)
    scores = jnp.where(mask, scores, NEG_INF)
    op_ref[0] = op_ref[0] + jnp.sum(mask.astype(jnp.int32), axis=1,
                                    keepdims=True)

    new_v, new_i = _fold_topk(ov_ref[0], oi_ref[0], scores, id_ref[0], k)
    ov_ref[0] = new_v
    oi_ref[0] = new_i


@functools.partial(
    jax.jit,
    static_argnames=("metric", "k", "q_block", "v_block", "interpret"),
)
def filtered_scan_tiled(
    slot_cluster: jax.Array,
    slot_tile: jax.Array,
    queries: jax.Array,
    lo: jax.Array,
    hi: jax.Array,
    vectors: jax.Array,
    attrs: jax.Array,
    ids: jax.Array,
    norms: Optional[jax.Array] = None,
    scales: Optional[jax.Array] = None,
    *,
    metric: str = "dot",
    k: int = 10,
    q_block: int = 64,
    v_block: int = 256,
    interpret: bool = False,
):
    """Tiled fused scan with streaming per-slot top-k.

    Grid: ``(S, Vpad // v_block)`` — unique-probe slots × intra-list blocks.
    Operands (scalar prefetch first):
      slot_cluster [S] int32      — cluster each slot scans          (SMEM)
      slot_tile    [S] int32      — query tile each slot serves      (SMEM)
      queries  [Qpad, D]          — Qpad a multiple of q_block; tile t is
                                    rows ``[t·QB, (t+1)·QB)``
      lo, hi   [Qpad, F, M] int16 — DNF interval bounds per query
      vectors  [K, Vpad, D], attrs [K, Vpad, M], ids [K, Vpad] — flat lists
      norms / scales [K, Vpad] f32 — l2 / SQ8 row constants

    Returns:
      vals  [S, QB, k] f32 — per-slot streaming top-k (NEG_INF pads)
      ids   [S, QB, k] int32 — original vector ids (-1 pads)
      npass [S, QB] int32 — candidates passing filter ∧ liveness per slot

    VMEM working set per step is ``QB·D + 4·QB·F·M + v_block·(D·bytes +
    M·2 + 8) + 2·QB·k`` — 64×768 queries + 256×768 bf16 block ≈ 0.6 MiB,
    far inside the ~16 MiB v5e budget, leaving room for double buffering.
    """
    s = slot_cluster.shape[0]
    qpad, d = queries.shape
    _, vpad, _ = vectors.shape
    m = attrs.shape[-1]
    f = lo.shape[1]
    if qpad % q_block:
        raise ValueError(f"Qpad={qpad} not a multiple of q_block={q_block}")
    v_block = min(v_block, vpad)
    while vpad % v_block != 0 and v_block > 8:
        v_block //= 2
    if vpad % v_block != 0:
        raise ValueError(f"vpad={vpad} has no usable v_block ≤ requested")
    if metric not in ("dot", "l2"):
        raise ValueError(metric)
    if metric == "l2":
        if norms is None:
            raise ValueError("metric='l2' requires norms")
        if scales is not None:
            raise NotImplementedError("SQ8 + l2 not wired (norms suffice)")

    nvb = vpad // v_block
    grid = (s, nvb)

    def im_query(si, vi, sc, st):
        del vi, sc
        return (st[si], 0)

    def im_vec(si, vi, sc, st):
        del st
        return (sc[si], vi, 0)

    def im_rows(si, vi, sc, st):
        del st
        return (sc[si], 0, vi)

    def im_out(si, vi, sc, st):
        del vi, sc, st
        return (si, 0, 0)

    # Mosaic tiles the last two block dims by (8, 128) unless a dim spans
    # the whole array, so every per-row operand gets a unit middle axis
    # ([K, 1, Vpad], block (1, 1, VB)) and npass comes back as [S, QB, 1].
    # Attributes go in as [K, M, Vpad]: with M=10 as the minor dim the TPU
    # layout pads it to 128 lanes, a 12.8× relayout copy of the whole
    # attribute table per call.  The DNF bounds are flattened to
    # [Qpad, F·M] int32 query rows.
    def rows(x):
        return x.reshape(x.shape[0], 1, x.shape[1])

    in_specs = [
        pl.BlockSpec((q_block, d), im_query),
        pl.BlockSpec((q_block, f * m), im_query),
        pl.BlockSpec((q_block, f * m), im_query),
        pl.BlockSpec((1, v_block, d), im_vec),
        pl.BlockSpec((1, m, v_block), im_rows),
        pl.BlockSpec((1, 1, v_block), im_rows),
    ]
    operands = [
        queries,
        lo.reshape(qpad, f * m).astype(jnp.int32),
        hi.reshape(qpad, f * m).astype(jnp.int32),
        vectors, jnp.swapaxes(attrs, 1, 2), rows(ids),
    ]
    quantized = scales is not None
    if metric == "l2":
        in_specs.append(pl.BlockSpec((1, 1, v_block), im_rows))
        operands.append(rows(norms))
    elif quantized:
        in_specs.append(pl.BlockSpec((1, 1, v_block), im_rows))
        operands.append(rows(scales))

    kernel = functools.partial(
        _tiled_kernel, k=k, metric=metric, quantized=quantized, n_terms=f,
        n_attrs=m,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, q_block, k), im_out),
            pl.BlockSpec((1, q_block, k), im_out),
            pl.BlockSpec((1, q_block, 1), im_out),
        ],
    )
    vals, out_ids, npass = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((s, q_block, k), jnp.float32),
            jax.ShapeDtypeStruct((s, q_block, k), jnp.int32),
            jax.ShapeDtypeStruct((s, q_block, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(slot_cluster.astype(jnp.int32), slot_tile.astype(jnp.int32), *operands)
    return vals, out_ids, npass[..., 0]


@functools.partial(jax.jit, static_argnames=("k",))
def fold_running_topk(
    run_vals: jax.Array,   # [QB, k] f32 running per-query top-k values
    run_ids: jax.Array,    # [QB, k] int32 running ids
    svals: jax.Array,      # [S, QB, k] f32 per-slot fragments (a segment)
    sids: jax.Array,       # [S, QB, k] int32
    alive: jax.Array,      # [QB, S] bool — (query, slot) pairs scheduled
    *,
    k: int,
):
    """Folds one scanned slot segment into the per-query running top-k.

    The bound-driven executor scans a tile's slot table in segments and
    compares the running kth score against the remaining slots' upper
    bounds; this is the device-side fold that keeps that running state —
    only the ``[QB, k]`` result crosses to host at segment boundaries, never
    the per-slot fragments (no host sync per tile/slot).  ``alive`` masks
    pairs that were dropped (or never scheduled), so the running kth can
    only reflect the surviving probe universe — folding a dropped pair's
    candidates could raise the kth above what that universe's full scan
    would produce and make a later drop unsound.
    """
    qb = svals.shape[1]
    live = alive.T[:, :, None]  # [S, QB, 1]
    vals = jnp.where(live, svals, NEG_INF)
    ids = jnp.where(live, sids, -1)
    vals = jnp.moveaxis(vals, 0, 1).reshape(qb, -1)  # [QB, S·k]
    ids = jnp.moveaxis(ids, 0, 1).reshape(qb, -1)
    vals = jnp.concatenate([run_vals, vals], axis=1)
    ids = jnp.concatenate([run_ids, ids], axis=1)
    new_vals, idx = jax.lax.top_k(vals, k)
    return new_vals, jnp.take_along_axis(ids, idx, axis=1)
