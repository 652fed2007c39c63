"""Hybrid IVF-Flat index structure and construction (paper §4.2).

Storage layout (TPU adaptation of the paper's per-list disk files):

  centroids : [K, D]        f32   — replicated; probed every query (§4.4 step 2)
  vectors   : [K, Vpad, D]  bf16  — padded flat lists, cluster-major. Sharded
                                    over chips on the leading axis at scale.
  attrs     : [K, Vpad, M]  int16 — attribute rows, same layout (§4.2 step 4)
  ids       : [K, Vpad]     int32 — original vector ids; -1 marks an empty or
                                    tombstoned slot
  norms     : [K, Vpad]     f32   — ||v||², only materialized for metric="l2"
  counts    : [K]           int32 — live-slot high-water mark per list

``Vpad`` is the static per-list capacity (multiple of the TPU lane width 128).
Padding is the price of static shapes; the roofline section quantifies the
waste (Vpad/V̄) and the build balances it by splitting oversized clusters.

The padded-scatter construction is pure JAX (sort + positional scatter, no
one-hot matmuls) so the same code path builds a 1k-vector test index on CPU
and a sharded billion-vector index under pjit.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hybrid import HybridSpec, make_hybrid
from repro.core import kmeans as kmeans_lib
from repro.core import summaries as summaries_lib
from repro.core.summaries import ClusterSummaries

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class IVFFlatIndex:
    spec: HybridSpec = dataclasses.field(metadata=dict(static=True))
    centroids: Array
    vectors: Array
    attrs: Array
    ids: Array
    counts: Array
    norms: Optional[Array] = None
    # SQ8 compression (beyond-paper, EXPERIMENTS §Perf): vectors stored int8
    # with a per-vector scale; halves the scan's HBM traffic (the dominant
    # roofline term) for ~1% recall cost. None ⇒ uncompressed bf16/f32.
    scales: Optional[Array] = None  # [K, Vpad] f32
    # Per-cluster attribute summaries (core/summaries.py): intervals +
    # histograms that let the probe planner prune clusters a query's filter
    # provably cannot match. None ⇒ planner never prunes.
    summaries: Optional[ClusterSummaries] = None

    @property
    def quantized(self) -> bool:
        return self.scales is not None

    @property
    def store_dtype(self):
        """Storage dtype of the flat lists (int8 under SQ8)."""
        return self.vectors.dtype

    @property
    def n_clusters(self) -> int:
        return self.centroids.shape[0]

    @property
    def vpad(self) -> int:
        return self.vectors.shape[1]

    @property
    def n_live(self) -> Array:
        return jnp.sum(self.counts)

    def nbytes(self) -> int:
        total = 0
        for f in (self.centroids, self.vectors, self.attrs, self.ids, self.counts):
            total += f.size * f.dtype.itemsize
        for opt in (self.norms, self.scales):
            if opt is not None:
                total += opt.size * opt.dtype.itemsize
        if self.summaries is not None:
            total += self.summaries.nbytes()
        return total


@dataclasses.dataclass(frozen=True)
class BuildStats:
    n_vectors: int
    n_dropped: int  # capacity overflow drops (0 unless vpad was forced too low)
    max_list_len: int
    mean_list_len: float
    vpad: int
    kmeans_steps: int


def default_n_clusters(n: int) -> int:
    """Paper §4.2/§4.3 heuristic: N/1000 small, sqrt(N) at scale."""
    if n <= 1_000_000:
        return max(1, n // 1000) or 1
    return int(np.sqrt(n))


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@functools.partial(jax.jit, static_argnames=("n_clusters", "vpad"))
def scatter_to_lists(
    values: Tuple[Array, ...], assignments: Array, n_clusters: int,
    vpad: int,
) -> Tuple[Tuple[Array, ...], Array, Array]:
    """Sorts rows by cluster and scatters each array into padded lists.

    ``values`` is a tuple of ``[N, ...]`` arrays in the same row order
    (vectors, attributes, ids).  Returns (tuple of lists ``[K, vpad, ...]``,
    slot_of_row [N], n_dropped scalar).  Rows beyond a list's capacity are
    dropped (mode="drop"), mirroring MoE capacity semantics; callers size
    vpad so drops are zero in practice.  One jitted program shares the sort
    between the arrays and fuses each sorted copy into its scatter: run
    eagerly, a multi-GB build holds three copies of the rows.
    """
    n = assignments.shape[0]
    order = jnp.argsort(assignments)  # stable
    a_sorted = jnp.take(assignments, order, axis=0)
    # position-within-cluster for sorted rows: arange - start_of_cluster
    starts = jnp.searchsorted(a_sorted, jnp.arange(n_clusters), side="left")
    pos = jnp.arange(n) - jnp.take(starts, a_sorted)
    lists = tuple(
        jnp.zeros((n_clusters, vpad) + v.shape[1:], v.dtype)
        .at[a_sorted, pos].set(jnp.take(v, order, axis=0), mode="drop")
        for v in values
    )
    dropped = jnp.sum((pos >= vpad).astype(jnp.int32))
    # slot index of each ORIGINAL row (for id→location bookkeeping)
    slot_of_row = jnp.zeros((n,), jnp.int32)
    slot_of_row = slot_of_row.at[order].set(pos.astype(jnp.int32))
    return lists, slot_of_row, dropped


def build_from_assignments(
    spec: HybridSpec,
    centroids: Array,
    core: Array,
    attrs: Array,
    assignments: Array,
    *,
    vpad: Optional[int] = None,
    ids: Optional[Array] = None,
    with_summaries: bool = True,
    summary_bins: int = summaries_lib.DEFAULT_N_BINS,
) -> Tuple[IVFFlatIndex, BuildStats]:
    """Builds the padded index given precomputed assignments (§4.2 steps 2-4).

    ``with_summaries`` (default) also builds the per-cluster attribute
    summaries the planner prunes with; ``summary_bins`` is the histogram
    width B.
    """
    core, attrs = make_hybrid(spec, core, attrs)
    n = core.shape[0]
    k = centroids.shape[0]
    counts = jax.ops.segment_sum(
        jnp.ones((n,), jnp.int32), assignments, num_segments=k
    )
    max_len = int(jnp.max(counts))
    if vpad is None:
        vpad = max(round_up(max_len, 128), 128)
    if ids is None:
        ids = jnp.arange(n, dtype=jnp.int32)

    (vec_lists, attr_lists, id_lists), _, dropped = scatter_to_lists(
        (core, attrs, ids.astype(jnp.int32)), assignments, k, vpad
    )
    id_init = jnp.full((k, vpad), -1, jnp.int32)
    # scatter_to_lists zero-fills; repaint empty slots with -1 sentinel.
    slot = jnp.arange(vpad)[None, :]
    live = slot < jnp.minimum(counts, vpad)[:, None]
    id_lists = jnp.where(live, id_lists, id_init)

    norms = None
    if spec.metric == "l2":
        norms = jnp.sum(
            vec_lists.astype(jnp.float32) ** 2, axis=-1
        )

    summ = (
        summaries_lib.build_summaries(attr_lists, id_lists, n_bins=summary_bins)
        if with_summaries and spec.n_attrs > 0 else None
    )
    index = IVFFlatIndex(
        spec=spec,
        centroids=centroids.astype(jnp.float32),
        vectors=vec_lists,
        attrs=attr_lists,
        ids=id_lists,
        counts=jnp.minimum(counts, vpad).astype(jnp.int32),
        norms=norms,
        summaries=summ,
    )
    stats = BuildStats(
        n_vectors=n,
        n_dropped=int(dropped),
        max_list_len=max_len,
        mean_list_len=float(jnp.mean(counts)),
        vpad=vpad,
        kmeans_steps=0,
    )
    return index, stats


def build_ivf(
    key: Array,
    spec: HybridSpec,
    core: Array,
    attrs: Array,
    *,
    n_clusters: Optional[int] = None,
    vpad: Optional[int] = None,
    kmeans_mode: str = "minibatch",
    kmeans_steps: int = 100,
    kmeans_batch: int = 4096,
    assign_chunk: int = 65536,
    ids: Optional[Array] = None,
    with_summaries: bool = True,
    summary_bins: int = summaries_lib.DEFAULT_N_BINS,
) -> Tuple[IVFFlatIndex, BuildStats]:
    """End-to-end index build (paper §4.2): centroids → assign → scatter.

    kmeans_mode: "minibatch" (paper's scalable path, [30]) or "lloyd"
    (paper's quality path) or "given" (pre-existing centroids passed via
    ``n_clusters``-sized ``core``-dtype array — the paper reuses LAION's
    prebuilt index; callers then use :func:`build_from_assignments`).
    """
    n = core.shape[0]
    k = n_clusters or default_n_clusters(n)
    # The k-means and assignment kernels widen rows to f32 themselves, a
    # block at a time; an up-front f32 copy of all N rows would double the
    # build's device footprint.
    core = jnp.asarray(core)
    if kmeans_mode == "minibatch":
        state = kmeans_lib.minibatch_kmeans(
            key,
            core,
            n_clusters=k,
            n_steps=kmeans_steps,
            batch_size=min(kmeans_batch, n),
        )
        centroids = state.centroids
    elif kmeans_mode == "lloyd":
        state, _ = kmeans_lib.kmeans_lloyd(
            key, core, n_clusters=k, n_iters=kmeans_steps
        )
        centroids = state.centroids
    else:
        raise ValueError(f"unknown kmeans_mode {kmeans_mode!r}")

    assignments = kmeans_lib.assign(core, centroids, chunk=assign_chunk)
    index, stats = build_from_assignments(
        spec, centroids, core, attrs, assignments, vpad=vpad, ids=ids,
        with_summaries=with_summaries, summary_bins=summary_bins,
    )
    return index, dataclasses.replace(stats, kmeans_steps=kmeans_steps)


def validity_mask(index: IVFFlatIndex) -> Array:
    """[K, Vpad] bool — live slots (within count and not tombstoned)."""
    slot = jnp.arange(index.vpad)[None, :]
    return jnp.logical_and(
        slot < index.counts[:, None], index.ids >= 0
    )


@jax.jit
def quantize_index(index: IVFFlatIndex) -> IVFFlatIndex:
    """SQ8: per-vector symmetric int8 quantization of the flat lists.

    score(q, v̂) = (q · v_int8) · scale reproduces q·v to ~0.4% relative
    error on unit-norm data; centroids stay f32 (probing is exact).  Jitted
    so the f32 widening stays inside one fused pass instead of a full f32
    copy of the lists.
    """
    if index.quantized:
        return index
    v32 = index.vectors.astype(jnp.float32)
    amax = jnp.max(jnp.abs(v32), axis=-1)  # [K, Vpad]
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(v32 / scale[..., None]), -127, 127).astype(jnp.int8)
    return dataclasses.replace(index, vectors=q, scales=scale)


def dequantize_rows(vectors: Array, scales: Array) -> Array:
    """[..., Vpad, D] int8 + [..., Vpad] scale → f32 rows."""
    return vectors.astype(jnp.float32) * scales[..., None]
