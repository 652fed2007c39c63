"""Serving loop: request batching, deadlines, straggler policy (paper §5.4).

The paper flags concurrent searches as a bottleneck for its single-host
design and suggests asynchronous request–reply patterns; this layer is that
pattern for the pod runtime:

  * requests (query vector + FilterSpec row) accumulate in a queue;
  * a micro-batcher drains up to ``max_batch`` requests or waits at most
    ``max_wait_s`` (padding the tail batch to the compiled static Q so the
    jitted search never recompiles);
  * per-batch deadline: chips reported unhealthy by the health tracker are
    excluded from the merge through ``shard_ok`` — the hierarchical top-k is
    an associative monoid, so partial merges return sound (lower-recall)
    results instead of timing out the whole batch;
  * health tracking is EWMA-on-failure with probation, mirroring what a real
    cluster's control plane feeds in.
"""

from __future__ import annotations

import dataclasses
import logging
import queue
import threading
import time
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.filters import FilterSpec, match_all
from repro.core.obs import span

Array = jax.Array
logger = logging.getLogger(__name__)


def make_fused_search_fn(index, *, k: int, n_probes: int, q_block: int = 64,
                         v_block: int = 256, backend: Optional[str] = None,
                         resident_budget_bytes: Optional[int] = None,
                         prune: str = "auto",
                         t_max=None,
                         pipeline: str = "auto",
                         pipeline_depth: int = 2,
                         adaptive_u_cap: Optional[bool] = None,
                         operand_cache: str = "auto",
                         u_cap_ladder: str = "pow2",
                         cache_shards: int = 1,
                         cache_transport: str = "loopback",
                         cache_l1_records: int = 64,
                         cache_fallback: bool = True,
                         peer_timeout_s: float = 30.0,
                         peer_retries: int = 1,
                         breaker_kwargs: Optional[dict] = None,
                         probe_interval_s: Optional[float] = None,
                         delta_budget_mb: Optional[float] = None,
                         delta_quantize: str = "auto",
                         device_cache_mb: Optional[float] = None,
                         termination: Optional[str] = None,
                         epsilon: float = 0.0,
                         partitions: str = "auto",
                         ) -> Callable:
    """The batched server's default search step: the search engine.

    Returns ``search_fn(queries, fspec, shard_ok) -> (scores, ids)`` wired
    to one long-lived :class:`repro.core.engine.SearchEngine` — the
    micro-batcher's whole purpose is assembling a query batch whose probes
    overlap, which is exactly what the engine's per-tile probe dedup
    converts into saved HBM traffic.  ``shard_ok`` is accepted (and ignored)
    so the same server drives the single-host and pod paths.

    ``index`` selects the tier: an in-RAM :class:`IVFFlatIndex`, an already
    open :class:`repro.core.disk.DiskIVFIndex`, or a checkpoint directory
    path (opened disk-resident under ``resident_budget_bytes``, with
    hot-cluster pinning).  Disk-tier batches run through the same kernel via
    the cache's pager and return identical results; the open index is
    exposed as ``search_fn.index`` (and the engine as ``search_fn.engine``)
    so callers can read ``resident_bytes()`` / cache / pipeline stats.

    Engine knobs: ``prune`` selects filter-aware probe pruning (``"auto"``
    = use the index's cluster attribute summaries when present); ``t_max``
    enables adaptive probe widening; ``pipeline`` (``"auto"`` = on for the
    disk tier) double-buffers per-tile cluster fetches against the scan —
    identical results, IO hidden behind compute; ``adaptive_u_cap``
    (default: on) provisions each batch's slot table from the observed
    post-prune unique-cluster counts in power-of-two buckets instead of the
    unpruned worst case — selective filters scan small tables, with at most
    ``len(buckets)`` scan compilations ever.

    Fetch-layer knobs: ``cache_shards > 1`` builds a consistent-hash
    :class:`~repro.core.blockstore.ShardedBlockStore` over that many peer
    caches of the same checkpoint (one index copy per pod) and routes the
    engine's fetch stage through it; ``cache_transport`` selects the peer
    transport (``"loopback"`` in-process, ``"socket"`` the length-prefixed
    wire protocol behind a local server per peer — the pod-topology
    rehearsal).  ``operand_cache`` fetches each cluster block through the
    store once per batch, letting the batch's tiles share the records;
    ``u_cap_ladder="fine"`` adds ×1.5 bucket
    midpoints.  The sharded store is exposed as ``search_fn.blockstore``
    (per-node stats via ``.stats()``) and torn down by
    ``search_fn.close()``.

    Resilience knobs (sharded fetch only): ``cache_fallback`` (default on)
    wires the index's own full-copy pager in as the availability floor —
    an unhealthy peer's clusters are served from local disk, results
    bit-identical, and the batch never fails; ``peer_timeout_s`` /
    ``peer_retries`` bound each socket fetch; ``breaker_kwargs`` tune the
    per-peer circuit breakers; ``probe_interval_s`` starts the active
    health probe.  ``search_fn.degraded()`` reports whether any peer
    circuit is currently open (the server marks responses accordingly).

    Live updates: ``delta_budget_mb`` attaches a RAM
    :class:`~repro.core.delta.DeltaTier` to a disk-tier index — new
    vectors land via ``search_fn.delta.add`` and are searchable in the
    very next batch; deletes via ``search_fn.delta.tombstone`` mask cold
    hits immediately.  ``search_fn.refresh()`` adopts a background
    ``delta.compact_deltas`` republish between batches (commits the
    folded delta rows out of RAM and flips the generation vector — the
    gen-keyed caches invalidate exactly the rewritten clusters).
    Requires a layout-v3 checkpoint (generation-tagged records).

    ``device_cache_mb`` attaches a cross-batch device-resident block cache
    (:class:`~repro.core.devicecache.DeviceBlockCache`) to a disk-tier
    index: hot clusters' fully-assembled operand blocks stay on device
    across batches under the byte budget, keyed ``(cluster_id, gen)`` and
    evicted by observed probe heat — repeat traffic pays no disk read, no
    peer RPC, no host assembly and no H2D transfer, and a republish
    invalidates exactly the rewritten entries via the same ``refresh()``
    handshake.  Stats under ``metrics()``'s ``device_cache.*`` keys; the
    cache is exposed as ``search_fn.device_cache``.

    ``termination`` selects the engine's recall-bounded execution mode:
    ``"exact"`` reorders each tile's probes best-bound-first and drops
    probes that provably cannot enter the top-k (bit-identical results,
    fewer segments scanned on selective streams); ``"bounded"`` with
    ``epsilon`` > 0 additionally drops probes whose probability of
    contributing a top-k hit is ≤ ε (recall ≥ 1−ε in expectation).
    ``delta_quantize="on"`` stores delta-tier rows SQ8-quantized even over
    a float cold tier (~4× capacity per MB; scores agree to quantization
    tolerance, and the next republish dequantizes the rows back into the
    cold tier's dtype).

    ``partitions`` controls filter-specialized sub-partition routing on a
    layout-v4 index (``"auto"`` = route when the index carries a partition
    catalog, ``"off"`` = always scan the flat layout, ``"on"`` = require a
    catalog): routed queries scan the narrowest sub-partition whose
    predicate subsumes their filter — bit-identical results, a fraction of
    the rows.
    """
    from repro.core import blockstore as blockstore_lib
    from repro.core.disk import DiskIVFIndex
    from repro.core.engine import SearchEngine

    owns_index = isinstance(index, str)
    if owns_index:
        index = DiskIVFIndex.open(
            index, resident_budget_bytes=resident_budget_bytes
        )
    delta = None
    if delta_budget_mb is not None:
        from repro.core import delta as delta_lib
        from repro.core import storage

        if not isinstance(index, DiskIVFIndex):
            raise ValueError(
                "delta_budget_mb needs a disk-tier index (a checkpoint "
                "path or an open DiskIVFIndex) — the RAM tier mutates in "
                "place via core.update instead"
            )
        if index.man["layout"] < 3:
            raise storage.GenerationMismatchError(
                f"delta_budget_mb needs a layout-v3 checkpoint "
                f"(generation-tagged cluster records); this one is layout "
                f"v{index.man['layout']} — re-save it with "
                f"storage.save_index(index, dir)"
            )
        delta = delta_lib.DeltaTier.for_index(
            index, delta_budget_mb, quantize=delta_quantize
        )
        index.delta = delta
    store = None
    if cache_shards > 1:
        if not isinstance(index, DiskIVFIndex):
            raise ValueError(
                "cache_shards > 1 needs a disk-tier index (a checkpoint "
                "path or an open DiskIVFIndex) — the RAM tier has no fetch "
                "stage to shard"
            )
        # per-node cache capacity: split the index's own cache budget so N
        # peers together hold what one local cache would have
        cap = max(index.cache.capacity_records // cache_shards, 1)
        # the pod's own full-copy pager (which otherwise idles while the
        # ring serves) is the availability floor: peer failures fetch
        # through it instead of failing the batch — zero extra memory
        store = blockstore_lib.open_sharded(
            index.directory, n_nodes=cache_shards,
            transport=cache_transport, capacity_records=cap,
            l1_records=cache_l1_records,
            fallback=index.blockstore if cache_fallback else None,
            timeout_s=peer_timeout_s, retries=peer_retries,
            breaker_kwargs=breaker_kwargs,
            probe_interval_s=probe_interval_s,
        )
    device_cache = None
    if device_cache_mb is not None:
        from repro.core.devicecache import DeviceBlockCache

        if not isinstance(index, DiskIVFIndex):
            raise ValueError(
                "device_cache_mb needs a disk-tier index (a checkpoint "
                "path or an open DiskIVFIndex) — the RAM tier's operands "
                "are already resident"
            )
        device_cache = DeviceBlockCache(
            blockstore_lib.BlockSpec.from_manifest(index.man),
            int(device_cache_mb * 2**20),
            heat_fn=index.cache.probe_heat,
        )
        index.device_cache = device_cache
    engine = SearchEngine(
        index, k=k, n_probes=n_probes, q_block=q_block, v_block=v_block,
        backend=backend, prune=prune, t_max=t_max, pipeline=pipeline,
        pipeline_depth=pipeline_depth, adaptive_u_cap=adaptive_u_cap,
        blockstore=store, operand_cache=operand_cache,
        u_cap_ladder=u_cap_ladder, device_cache=device_cache,
        termination=termination, epsilon=epsilon,
        partitions=partitions,
    )

    def search_fn(queries, fspec, shard_ok=None):
        del shard_ok  # single host; the pod path lives in core/distributed
        res = engine.search(queries, fspec)
        return res.scores, res.ids

    def close():
        engine.close()
        if store is not None:
            store.close()
        # only tear down an index this factory opened (str path) — a
        # caller-provided DiskIVFIndex may back other search_fns
        if owns_index:
            index.close()

    search_fn.index = index
    search_fn.engine = engine
    search_fn.blockstore = engine.blockstore
    search_fn.degraded = (
        lambda: bool(getattr(engine.blockstore, "degraded", False))
    )
    search_fn.delta = delta
    search_fn.device_cache = device_cache
    search_fn.refresh = engine.refresh
    search_fn.metrics = engine.metrics
    search_fn.metrics_text = engine.metrics_text
    search_fn.close = close
    return search_fn


class Reply(queue.Queue):
    """One request's delivery channel (size 1).

    ``get`` returns the :class:`Response`, or raises the exception that the
    request's batch failed with, so a failed search reaches its caller
    instead of leaving it blocked until its timeout.
    """

    def __init__(self):
        super().__init__(maxsize=1)

    def get(self, block: bool = True, timeout: Optional[float] = None):
        item = super().get(block, timeout)
        if isinstance(item, BaseException):
            raise item
        return item


@dataclasses.dataclass
class Request:
    query: np.ndarray  # [D]
    lo: np.ndarray  # [F, M] int16
    hi: np.ndarray  # [F, M]
    future: Reply  # delivery channel (size 1)
    t_enqueue: float = 0.0


@dataclasses.dataclass
class Response:
    scores: np.ndarray  # [k]
    ids: np.ndarray  # [k]
    latency_s: float
    batched_with: int
    degraded: bool  # a shard was dropped from the merge, or the fetch
    #                 layer served around an open peer circuit (the latter
    #                 keeps results bit-identical — it is a health signal,
    #                 not a recall warning)
    batch: int  # the server's batch number: the stat ``batch`` of the
    #             batch's ``repro.server.*`` spans


class ShardHealth:
    """EWMA failure tracker per shard; drops a shard from merges while its
    failure score exceeds the threshold, then lets it back in (probation)."""

    def __init__(self, n_shards: int, threshold: float = 0.5,
                 decay: float = 0.8):
        self.n = n_shards
        self.threshold = threshold
        self.decay = decay
        self.score = np.zeros(n_shards)

    def report(self, shard: int, failed: bool):
        self.score[shard] = self.decay * self.score[shard] + (
            (1 - self.decay) if failed else 0.0
        )

    def ok_mask(self) -> np.ndarray:
        return self.score <= self.threshold

    @property
    def degraded(self) -> bool:
        return bool((~self.ok_mask()).any())


class SearchServer:
    """Micro-batching server around a compiled ``search_fn``.

    search_fn(queries [Q, D], fspec, shard_ok [S]) -> (scores [Q,k], ids [Q,k])
    with STATIC Q — the server pads tail batches.

    The serving loop is tiled by five spans (``repro.core.obs.span``), each
    with the batch number as stat ``batch`` and a running sum of seconds in
    ``stats``: ``server.drain`` (``drain_s``, waiting until a batch is
    formed), ``server.assemble`` (``assemble_s``, stacking the requests and
    the host-to-device puts), ``server.dispatch`` (``dispatch_s``, the call
    into ``search_fn``), ``server.wait`` (``wait_s``, the answers to the
    host: device completion and copy) and ``server.deliver``
    (``deliver_s``, bookkeeping and the responses).  ``total_latency_s`` is
    ``dispatch_s + wait_s``, updated in the same step.
    """

    def __init__(
        self,
        search_fn: Callable,
        *,
        batch_size: int,
        dim: int,
        n_attrs: int,
        n_terms: int,
        n_shards: int,
        max_wait_s: float = 0.005,
    ):
        self.search_fn = search_fn
        self.batch_size = batch_size
        self.dim = dim
        self.n_attrs = n_attrs
        self.n_terms = n_terms
        self.max_wait_s = max_wait_s
        self.health = ShardHealth(n_shards)
        self._q: "queue.Queue[Request]" = queue.Queue()
        self._stop = threading.Event()
        self._refresh = threading.Event()
        self._worker: Optional[threading.Thread] = None
        self.stats = dict(batches=0, requests=0, degraded_batches=0,
                          failed_batches=0, total_latency_s=0.0,
                          refreshes=0, drain_s=0.0, assemble_s=0.0,
                          dispatch_s=0.0, wait_s=0.0, deliver_s=0.0)
        self._next_batch = 0  # the number of the batch being formed

    # ---- client side ----
    def submit(self, query: np.ndarray, fspec_row: Optional[Tuple] = None
               ) -> Reply:
        if fspec_row is None:
            wild = match_all(1, self.n_attrs, self.n_terms)
            lo, hi = np.asarray(wild.lo[0]), np.asarray(wild.hi[0])
        else:
            lo, hi = fspec_row
        fut = Reply()
        self._q.put(Request(np.asarray(query), np.asarray(lo),
                            np.asarray(hi), fut, time.monotonic()))
        return fut

    def search_blocking(self, query, fspec_row=None, timeout=60.0) -> Response:
        return self.submit(query, fspec_row).get(timeout=timeout)

    # ---- server side ----
    def start(self):
        self._worker = threading.Thread(target=self._run, daemon=True)
        self._worker.start()

    def stop(self):
        self._stop.set()
        if self._worker:
            self._worker.join(timeout=30)

    def _drain(self) -> List[Request]:
        """Assembles the next micro-batch.

        The batch deadline is anchored at the *oldest request's enqueue
        time* (``t_enqueue + max_wait_s``), not at drain start: a request
        that aged in the queue while the previous batch was being served,
        or a slow trickle of arrivals each landing just inside the old
        per-``get`` timeout, can no longer stretch batch assembly.  Once
        the deadline passes, only requests already sitting in the queue are
        swept in (they cost no extra latency) and the batch is served.
        """
        batch: List[Request] = []
        deadline = None
        while len(batch) < self.batch_size and not self._stop.is_set():
            now = time.monotonic()
            if deadline is not None and now >= deadline:
                break
            timeout = self.max_wait_s if deadline is None else deadline - now
            try:
                req = self._q.get(timeout=max(timeout, 1e-4))
            except queue.Empty:
                if batch:
                    break
                continue
            batch.append(req)
            if deadline is None:
                deadline = req.t_enqueue + self.max_wait_s
        # Deadline hit or batch full: take whatever is already queued
        # (non-blocking) — free batching, zero added wait.
        while batch and len(batch) < self.batch_size:
            try:
                batch.append(self._q.get_nowait())
            except queue.Empty:
                break
        return batch

    def request_refresh(self):
        """Asks the serving loop to adopt a republished checkpoint.

        Safe from any thread (a background ``compact_deltas`` caller, an
        operator signal): the flag is drained *between* batches, so the
        generation flip never races a batch mid-flight — the atomic
        no-drain handshake of the hot/cold tier.  A no-op for search_fns
        without a ``refresh`` attribute.
        """
        self._refresh.set()

    def _maybe_refresh(self):
        if not self._refresh.is_set():
            return
        self._refresh.clear()
        refresh = getattr(self.search_fn, "refresh", None)
        if callable(refresh):
            refresh()
            self.stats["refreshes"] += 1

    def _span(self, stage: str, batch: int) -> span:
        """Span ``repro.server.<stage>`` adding its seconds to
        ``stats["<stage>_s"]``."""
        key = f"{stage}_s"

        def sink(seconds: float):
            self.stats[key] += seconds

        return span(f"server.{stage}", sink, batch=batch)

    def _run(self):
        while not self._stop.is_set():
            self._maybe_refresh()
            n = self._next_batch
            with self._span("drain", n):
                batch = self._drain()
            if not batch:
                continue
            self._next_batch = n + 1
            try:
                self._serve(batch, n)
            except Exception as e:  # the loop must outlive a failed batch
                logger.exception("search batch of %d requests failed",
                                 len(batch))
                self.stats["failed_batches"] += 1
                for r in batch:
                    if r.future.empty():
                        r.future.put(e)

    def _serve(self, batch: List[Request], n: int):
        b = len(batch)
        qsz = self.batch_size
        with self._span("assemble", n):
            queries = np.zeros((qsz, self.dim), np.float32)
            lo = np.zeros((qsz, self.n_terms, self.n_attrs), np.int16)
            hi = np.zeros((qsz, self.n_terms, self.n_attrs), np.int16)
            for i, r in enumerate(batch):
                queries[i] = r.query
                lo[i] = r.lo
                hi[i] = r.hi
            fspec = FilterSpec(lo=jnp.asarray(lo), hi=jnp.asarray(hi))
            queries = jnp.asarray(queries)
            ok = jnp.asarray(self.health.ok_mask())
        # dispatch and wait reach stats together with total_latency_s in
        # the deliver step, so every copy of stats has it equal to their sum
        with span("server.dispatch", None, batch=n) as dispatch:
            scores, ids = self.search_fn(queries, fspec, ok)
        with span("server.wait", None, batch=n) as wait:
            scores = np.asarray(scores)
            ids = np.asarray(ids)
        t1 = time.monotonic()
        with self._span("deliver", n):
            # degraded = a shard dropped from the merge OR the fetch layer
            # routing around an open peer circuit (results stay
            # bit-identical in the latter case; clients still deserve the
            # signal)
            store_degraded = getattr(self.search_fn, "degraded", None)
            degraded = self.health.degraded or bool(
                store_degraded() if callable(store_degraded) else False
            )
            st = self.stats
            dispatch_s = st["dispatch_s"] + dispatch.seconds
            wait_s = st["wait_s"] + wait.seconds
            st.update(batches=st["batches"] + 1,
                      requests=st["requests"] + b,
                      degraded_batches=st["degraded_batches"] + int(degraded),
                      dispatch_s=dispatch_s, wait_s=wait_s,
                      total_latency_s=dispatch_s + wait_s)
            for i, r in enumerate(batch):
                r.future.put(
                    Response(
                        scores=scores[i],
                        ids=ids[i],
                        latency_s=t1 - r.t_enqueue,
                        batched_with=b,
                        degraded=degraded,
                        batch=n,
                    )
                )
