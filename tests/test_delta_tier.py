"""Live-updating hot/cold tiered serving: the RAM delta tier + generation-
tagged cluster blocks.

The invariant under test, end to end: for ANY interleaving of add /
tombstone / compact_deltas / refresh, search results are BIT-IDENTICAL to a
from-scratch rebuild of the index at the same logical state — across
metrics × SQ8 × prune × pipeline, under the local and sharded stores, and
with a peer lagging (or killed) mid-republish.  ``n_scanned``/``n_passed``
are deliberately excluded: the delta scan and in-scan tombstone masking
count work differently from a rebuild, by design.

Generation precision: a republish must invalidate exactly the rewritten
``(cluster_id, gen)`` cache entries — asserted via the cache/L1
invalidation counters — and a stale peer answer must be re-fetched, never
silently served.
"""

import os
import shutil

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    DeltaOverflowError,
    DeltaTier,
    FilterSpec,
    GenerationMismatchError,
    HybridSpec,
    compact_deltas,
    compact_stale,
    match_all,
    stale_counts,
    storage,
)
from repro.core import blockstore as bs
from repro.core import faults
from repro.core import kmeans as kmeans_lib
from repro.core import update as update_lib
from repro.core.disk import DiskIVFIndex
from repro.core.engine import SearchEngine
from repro.core.ivf import build_from_assignments, quantize_index
from repro.core.serving import make_fused_search_fn

N, D, M, KC = 1536, 32, 6, 12
TS_RANGE = 6000
K, NP, QB = 10, 5, 8


def _topic_data(seed=3):
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((KC, D)).astype(np.float32)
    centers /= np.linalg.norm(centers, axis=-1, keepdims=True)
    topic = (np.arange(N) * KC) // N
    core = centers[topic] + 0.05 * rng.standard_normal((N, D)).astype(
        np.float32
    )
    core /= np.linalg.norm(core, axis=-1, keepdims=True)
    band = TS_RANGE // KC
    attrs = rng.integers(0, 16, (N, M)).astype(np.int16)
    attrs[:, 0] = (topic * band + rng.integers(0, band, N)).astype(np.int16)
    return centers, core, attrs, topic


def _build(metric, quantized):
    centers, core, attrs, topic = _topic_data()
    spec = HybridSpec(dim=D, n_attrs=M, core_dtype=jnp.float32,
                      metric=metric)
    # vpad headroom so republished clusters can absorb folded delta rows
    vpad = int(np.bincount(topic, minlength=KC).max()) + 96
    index, _ = build_from_assignments(
        spec, jnp.asarray(centers), jnp.asarray(core), jnp.asarray(attrs),
        jnp.asarray(topic), vpad=vpad, ids=jnp.arange(N),
    )
    if quantized:
        index = quantize_index(index)
    return index, centers, core, attrs, topic


class Logical:
    """The ground-truth logical state a rebuild oracle is built from:
    every row ever added (checkpoint rows first, then delta adds in add
    order), with a liveness mask."""

    def __init__(self, centers, core, attrs, topic):
        self.centers = centers
        self.core = core.copy()
        self.attrs = attrs.copy()
        self.ids = np.arange(len(core))
        self.clusters = topic.copy().astype(np.int64)
        self.alive = np.ones(len(core), bool)
        self.next_id = len(core)

    def add(self, core, attrs):
        ids = np.arange(self.next_id, self.next_id + len(core))
        self.next_id += len(core)
        a = np.asarray(
            kmeans_lib.assign(jnp.asarray(core), jnp.asarray(self.centers))
        )
        self.core = np.concatenate([self.core, core])
        self.attrs = np.concatenate([self.attrs, attrs])
        self.ids = np.concatenate([self.ids, ids])
        self.clusters = np.concatenate([self.clusters, a.astype(np.int64)])
        self.alive = np.concatenate([self.alive, np.ones(len(core), bool)])
        return ids

    def kill(self, ids):
        self.alive[np.isin(self.ids, ids)] = False

    def cluster_of(self, ids):
        pos = np.searchsorted(self.ids, ids)
        return self.clusters[pos]

    def oracle_engine(self, spec, quantized, **engine_kw):
        m = self.alive
        idx, _ = build_from_assignments(
            spec, jnp.asarray(self.centers), jnp.asarray(self.core[m]),
            jnp.asarray(self.attrs[m]), jnp.asarray(self.clusters[m]),
            ids=jnp.asarray(self.ids[m]),
        )
        if quantized:
            idx = quantize_index(idx)
        return SearchEngine(idx, **engine_kw)


def _window_fspec(q, width, seed=7):
    rng = np.random.default_rng(seed)
    lo = np.full((q, 1, M), -32768, np.int16)
    hi = np.full((q, 1, M), 32767, np.int16)
    start = rng.integers(0, max(TS_RANGE - width, 1), q)
    lo[:, 0, 0] = start.astype(np.int16)
    hi[:, 0, 0] = (start + width - 1).astype(np.int16)
    return FilterSpec(lo=jnp.asarray(lo), hi=jnp.asarray(hi))


def _assert_results_equal(live, oracle, msg=""):
    np.testing.assert_array_equal(np.asarray(live.ids),
                                  np.asarray(oracle.ids), err_msg=msg)
    np.testing.assert_array_equal(np.asarray(live.scores),
                                  np.asarray(oracle.scores), err_msg=msg)


@pytest.fixture(scope="module", params=[
    ("dot", False), ("l2", False), ("dot", True), ("l2", True),
], ids=["dot-f32", "l2-f32", "dot-sq8", "l2-sq8"])
def built_all(request):
    metric, quantized = request.param
    return _build(metric, quantized) + (metric, quantized)


@pytest.fixture(scope="module")
def built_dot():
    return _build("dot", False)


def _open_live(index, ckpt_dir, budget_mb=8.0):
    storage.save_index(index, ckpt_dir, n_shards=2)
    disk = DiskIVFIndex.open(ckpt_dir)
    tier = DeltaTier.for_index(disk, budget_mb)
    disk.delta = tier
    return disk, tier


# ---------------------------------------------------------------------------
# Parity matrix: metric × SQ8 × prune × pipeline, pre- and post-republish
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("pipeline", ["off", "on"])
@pytest.mark.parametrize("prune", ["off", "on"])
def test_delta_parity_matrix(built_all, prune, pipeline, tmp_path):
    index, centers, core, attrs, topic, metric, quantized = built_all
    disk, tier = _open_live(index, str(tmp_path / "ck"))
    state = Logical(centers, core, attrs, topic)
    rng = np.random.default_rng(11)

    eng = SearchEngine(disk, k=K, n_probes=NP, q_block=QB,
                       prune=prune, pipeline=pipeline)
    kw = dict(k=K, n_probes=NP, q_block=QB, prune=prune)
    q = 21  # ragged multi-tile at q_block=8
    queries = jnp.asarray(core[5:5 + q] + 0.01)
    specs = {"all": match_all(q, M), "window": _window_fspec(q, 900)}

    # adds + cold tombstones + delta tombstones, then check both filters
    add_core = (centers[rng.integers(0, KC, 60)]
                + 0.05 * rng.standard_normal((60, D))).astype(np.float32)
    add_core /= np.linalg.norm(add_core, axis=-1, keepdims=True)
    add_attrs = rng.integers(0, TS_RANGE, (60, M)).astype(np.int16)
    new_ids = state.add(add_core, add_attrs)
    tier.add(add_core, add_attrs, new_ids)

    cold_dead = rng.choice(N, 40, replace=False)
    tier.tombstone(cold_dead, clusters=topic[cold_dead])
    state.kill(cold_dead)
    delta_dead = new_ids[:7]
    tier.tombstone(delta_dead)
    state.kill(delta_dead)

    oracle = state.oracle_engine(index.spec, quantized, **kw)
    for name, fs in specs.items():
        _assert_results_equal(eng.search(queries, fs),
                              oracle.search(queries, fs),
                              f"pre-republish {name}")

    # republish + between-batch adoption: same logical state, delta empty
    st = compact_deltas(str(tmp_path / "ck"), tier)
    assert st.clusters_rewritten > 0 and st.rows_folded == 53  # 60 − 7 dead
    assert eng.refresh()
    assert tier.stats()["rows"] == 0
    for name, fs in specs.items():
        _assert_results_equal(eng.search(queries, fs),
                              oracle.search(queries, fs),
                              f"post-republish {name}")
    assert eng.stats.delta_folds > 0
    eng.close()
    oracle.close()
    disk.close()


# ---------------------------------------------------------------------------
# Tombstones mask cold hits immediately; the (k+1)-th candidate surfaces
# ---------------------------------------------------------------------------


def test_tombstone_surfaces_next_candidate(built_dot, tmp_path):
    index, centers, core, attrs, topic = built_dot
    disk, tier = _open_live(index, str(tmp_path / "ck"))
    eng = SearchEngine(disk, k=K, n_probes=NP, q_block=QB)
    q = jnp.asarray(core[100:101])
    fs = match_all(1, M)
    before = eng.search(q, fs)
    top = int(np.asarray(before.ids)[0, 0])
    runner_up = np.asarray(before.ids)[0, 1:]

    tier.tombstone(np.asarray([top]), clusters=np.asarray([topic[top]]))
    after = eng.search(q, fs)
    ids_after = np.asarray(after.ids)[0]
    assert top not in ids_after
    # the old ranks 2..k shift up one; a fresh (k+1)-th candidate fills in
    np.testing.assert_array_equal(ids_after[:K - 1], runner_up)
    assert ids_after[K - 1] >= 0
    eng.close()
    disk.close()


def test_delta_add_visible_next_batch(built_dot, tmp_path):
    index, centers, core, attrs, topic = built_dot
    disk, tier = _open_live(index, str(tmp_path / "ck"))
    eng = SearchEngine(disk, k=K, n_probes=NP, q_block=QB)
    rng = np.random.default_rng(5)
    v = core[200] + 0.001 * rng.standard_normal(D).astype(np.float32)
    v = (v / np.linalg.norm(v)).astype(np.float32)
    tier.add(v[None], np.zeros((1, M), np.int16), np.asarray([N + 1]))
    res = eng.search(jnp.asarray(v[None]), match_all(1, M))
    assert int(np.asarray(res.ids)[0, 0]) == N + 1  # its own NN, next batch
    eng.close()
    disk.close()


# ---------------------------------------------------------------------------
# Randomized interleaving: add/tombstone/compact/publish in random order,
# bit-identical to a rebuild at every step
# ---------------------------------------------------------------------------


def test_randomized_interleaving_bit_identity(built_dot, tmp_path):
    index, centers, core, attrs, topic = built_dot
    ck = str(tmp_path / "ck")
    disk, tier = _open_live(index, ck)
    state = Logical(centers, core, attrs, topic)
    rng = np.random.default_rng(23)
    eng = SearchEngine(disk, k=K, n_probes=NP, q_block=QB)
    kw = dict(k=K, n_probes=NP, q_block=QB)
    q = 9
    queries = jnp.asarray(core[40:40 + q] + 0.01)
    fs = match_all(q, M)

    for step in range(12):
        op = rng.integers(0, 4)
        if op == 0:  # add a batch
            b = int(rng.integers(1, 16))
            add = (centers[rng.integers(0, KC, b)]
                   + 0.05 * rng.standard_normal((b, D))).astype(np.float32)
            aat = rng.integers(0, TS_RANGE, (b, M)).astype(np.int16)
            tier.add(add, aat, state.add(add, aat))
        elif op == 1:  # tombstone random live ids (cold or delta)
            live = state.ids[state.alive]
            dead = rng.choice(live, min(6, len(live)), replace=False)
            tier.tombstone(dead, clusters=state.cluster_of(dead))
            state.kill(dead)
        elif op == 2:  # background republish + between-batch adoption
            compact_deltas(ck, tier)
            eng.refresh()
        # op == 3: just search
        res = eng.search(queries, fs)
        oracle = state.oracle_engine(index.spec, False, **kw)
        _assert_results_equal(res, oracle.search(queries, fs),
                              f"step {step} op {op}")
        oracle.close()
    eng.close()
    disk.close()


# ---------------------------------------------------------------------------
# Generation precision: a republish invalidates exactly the rewritten
# (cluster, gen) entries
# ---------------------------------------------------------------------------


def test_republish_invalidates_only_rewritten(built_dot, tmp_path):
    index, centers, core, attrs, topic = built_dot
    ck = str(tmp_path / "ck")
    disk, tier = _open_live(index, ck)
    eng = SearchEngine(disk, k=K, n_probes=KC, q_block=QB)  # probe all
    q = jnp.asarray(core[:8])
    fs = match_all(8, M)
    eng.search(q, fs)
    cached = set(disk.cache._entries)
    assert cached == set(range(KC))  # everything cached

    # tombstone rows in exactly two clusters → republish touches only them
    victims = np.concatenate([
        np.nonzero(topic == 2)[0][:3], np.nonzero(topic == 9)[0][:3],
    ])
    tier.tombstone(victims, clusters=topic[victims])
    st = compact_deltas(ck, tier)
    assert st.clusters_rewritten == 2
    eng.refresh()
    assert np.count_nonzero(disk.gens) == 2

    base = disk.cache.stats.invalidations
    eng.search(q, fs)
    assert disk.cache.stats.invalidations - base == 2  # exactly the two
    # the other ten records never left the cache (no extra misses for them)
    eng.close()
    disk.close()


def test_sharded_l1_invalidates_only_rewritten(built_dot, tmp_path):
    index, centers, core, attrs, topic = built_dot
    ck = str(tmp_path / "ck")
    storage.save_index(index, ck, n_shards=2)
    store = bs.open_sharded(ck, n_nodes=3, l1_records=KC, self_node=None)
    disk = DiskIVFIndex.open(ck)
    tier = DeltaTier.for_index(disk, 8.0)
    disk.delta = tier
    eng = SearchEngine(disk, k=K, n_probes=KC, q_block=QB, blockstore=store)
    q = jnp.asarray(core[:8])
    fs = match_all(8, M)
    eng.search(q, fs)
    l1_before = set(store._l1)

    victims = np.nonzero(topic == 4)[0][:3]
    tier.tombstone(victims, clusters=topic[victims])
    compact_deltas(ck, tier)
    eng.refresh()  # refreshes the ring (owned stores + fallback) + index
    eng.search(q, fs)
    assert store.l1_invalidations == (1 if 4 in l1_before else 0)
    assert store.store_stats.stale_answers == 0  # peers were refreshed
    eng.close()
    store.close()
    disk.close()


# ---------------------------------------------------------------------------
# Sharded ring: stale peer answers are re-fetched, never silently served
# ---------------------------------------------------------------------------


class _StripGens:
    """A peer stuck on the pre-gen wire: forwards fetches without the
    expected generations, so a lagging server answers stale."""

    def __init__(self, inner):
        self.inner = inner

    def fetch(self, cluster_ids, gens=None):
        return self.inner.fetch(cluster_ids)  # drops gens

    def ping(self):
        self.inner.ping()

    def stats(self):
        return self.inner.stats()

    def close(self):
        self.inner.close()


def test_stale_peer_answer_refetched(built_dot, tmp_path):
    index, centers, core, attrs, topic = built_dot
    ck = str(tmp_path / "ck")
    storage.save_index(index, ck, n_shards=2)
    store = bs.open_sharded(ck, n_nodes=3, l1_records=4, self_node=None)
    disk = DiskIVFIndex.open(ck)
    tier = DeltaTier.for_index(disk, 8.0)
    disk.delta = tier
    state = Logical(centers, core, attrs, topic)
    eng = SearchEngine(disk, k=K, n_probes=KC, q_block=QB, blockstore=store)
    q = jnp.asarray(core[:8])
    fs = match_all(8, M)
    eng.search(q, fs)  # warm every peer's mmaps + caches

    rng = np.random.default_rng(31)
    add = (centers[np.arange(KC)]
           + 0.05 * rng.standard_normal((KC, D))).astype(np.float32)
    aat = rng.integers(0, TS_RANGE, (KC, M)).astype(np.int16)
    tier.add(add, aat, state.add(add, aat))  # every cluster rewritten
    compact_deltas(ck, tier)

    # node 1 lags the republish: its reader never reopens AND its wire
    # predates gen stamping (otherwise the gen-aware cache self-heals)
    lag = 1
    store.transports[lag] = _StripGens(store.transports[lag])
    store._owned_stores[lag].refresh = lambda: None
    eng.refresh()

    res = eng.search(q, fs)
    assert store.store_stats.stale_answers > 0
    oracle = state.oracle_engine(index.spec, False, k=K, n_probes=KC,
                                 q_block=QB)
    _assert_results_equal(res, oracle.search(q, fs), "lagging peer")
    oracle.close()
    eng.close()
    store.close()
    disk.close()


def test_lagging_peer_self_heals_with_gen_stamped_fetch(built_dot, tmp_path):
    index, centers, core, attrs, topic = built_dot
    ck = str(tmp_path / "ck")
    storage.save_index(index, ck, n_shards=2)
    store = bs.open_sharded(ck, n_nodes=2, l1_records=4, self_node=None)
    disk = DiskIVFIndex.open(ck)
    tier = DeltaTier.for_index(disk, 8.0)
    disk.delta = tier
    state = Logical(centers, core, attrs, topic)
    eng = SearchEngine(disk, k=K, n_probes=KC, q_block=QB, blockstore=store)
    q = jnp.asarray(core[:8])
    fs = match_all(8, M)
    eng.search(q, fs)

    rng = np.random.default_rng(37)
    add = (centers[np.arange(KC)]
           + 0.05 * rng.standard_normal((KC, D))).astype(np.float32)
    aat = rng.integers(0, TS_RANGE, (KC, M)).astype(np.int16)
    tier.add(add, aat, state.add(add, aat))
    compact_deltas(ck, tier)

    # peer 0 lags, but gen-stamped fetches reach it: its cache detects the
    # stale generation, reopens its own reader, and serves fresh
    store._owned_stores[0].refresh = lambda: None
    eng.refresh()
    res = eng.search(q, fs)
    assert store.store_stats.stale_answers == 0
    assert store._owned_stores[0].cache.stats.invalidations > 0
    oracle = state.oracle_engine(index.spec, False, k=K, n_probes=KC,
                                 q_block=QB)
    _assert_results_equal(res, oracle.search(q, fs), "self-healed peer")
    oracle.close()
    eng.close()
    store.close()
    disk.close()


def test_kill_peer_mid_republish(built_dot, tmp_path):
    index, centers, core, attrs, topic = built_dot
    ck = str(tmp_path / "ck")
    storage.save_index(index, ck, n_shards=2)
    store = bs.open_sharded(ck, n_nodes=3, l1_records=4, self_node=None)
    disk = DiskIVFIndex.open(ck)
    tier = DeltaTier.for_index(disk, 8.0)
    disk.delta = tier
    state = Logical(centers, core, attrs, topic)
    eng = SearchEngine(disk, k=K, n_probes=KC, q_block=QB, blockstore=store)
    q = jnp.asarray(core[:8])
    fs = match_all(8, M)
    eng.search(q, fs)

    rng = np.random.default_rng(41)
    add = (centers[np.arange(KC)]
           + 0.05 * rng.standard_normal((KC, D))).astype(np.float32)
    aat = rng.integers(0, TS_RANGE, (KC, M)).astype(np.int16)
    tier.add(add, aat, state.add(add, aat))

    # the peer dies between the republish and the flip — the exact window
    # where a stale block could slip through without gen tagging
    faults.inject(store, 1, faults.kill_peer(after=0))
    compact_deltas(ck, tier)
    eng.refresh()
    res = eng.search(q, fs)
    s = store.stats()
    assert s["failovers"] + s["redirected_blocks"] > 0
    oracle = state.oracle_engine(index.spec, False, k=K, n_probes=KC,
                                 q_block=QB)
    _assert_results_equal(res, oracle.search(q, fs), "killed peer")
    oracle.close()
    eng.close()
    store.close()
    disk.close()


# ---------------------------------------------------------------------------
# Freeze/commit handshake: tombstones racing a pending republish
# ---------------------------------------------------------------------------


def test_late_tombstone_during_pending_republish(built_dot, tmp_path):
    index, centers, core, attrs, topic = built_dot
    ck = str(tmp_path / "ck")
    disk, tier = _open_live(index, ck)
    state = Logical(centers, core, attrs, topic)
    rng = np.random.default_rng(43)
    eng = SearchEngine(disk, k=K, n_probes=NP, q_block=QB)
    kw = dict(k=K, n_probes=NP, q_block=QB)
    q = jnp.asarray(core[60:69] + 0.01)
    fs = match_all(9, M)

    add = (centers[rng.integers(0, KC, 20)]
           + 0.05 * rng.standard_normal((20, D))).astype(np.float32)
    aat = rng.integers(0, TS_RANGE, (20, M)).astype(np.int16)
    new_ids = state.add(add, aat)
    tier.add(add, aat, new_ids)

    compact_deltas(ck, tier)  # freeze + rewrite; NOT yet adopted
    assert tier.stats()["pending"]
    # a frozen (already-folded) row dies while the republish is pending
    late = new_ids[:4]
    tier.tombstone(late)
    state.kill(late)

    # pre-adoption: old cold view + delta minus the late-dead rows
    oracle = state.oracle_engine(index.spec, False, **kw)
    _assert_results_equal(eng.search(q, fs), oracle.search(q, fs),
                          "pending republish")
    # adoption: the republished cold copy CONTAINS the folded rows; the
    # carried-over tombstones must keep masking them
    assert eng.refresh()
    assert not tier.stats()["pending"]
    _assert_results_equal(eng.search(q, fs), oracle.search(q, fs),
                          "after adoption")
    # a second republish reclaims them from the cold tier for good
    compact_deltas(ck, tier)
    eng.refresh()
    assert tier.stats()["tombstones"] == 0
    _assert_results_equal(eng.search(q, fs), oracle.search(q, fs),
                          "after second republish")
    oracle.close()
    eng.close()
    disk.close()


def test_delta_overflow_is_loud(built_dot, tmp_path):
    index, centers, core, attrs, topic = built_dot
    disk, _ = _open_live(index, str(tmp_path / "ck"))
    tier = DeltaTier(disk, capacity=4)
    a = np.zeros((3, M), np.int16)
    tier.add(core[:3], a, np.asarray([9000, 9001, 9002]))
    with pytest.raises(DeltaOverflowError):
        tier.add(core[3:6], a, np.asarray([9003, 9004, 9005]))
    assert tier.stats()["rows"] == 3  # failed add landed nothing
    disk.close()


# ---------------------------------------------------------------------------
# Back-compat + typed errors
# ---------------------------------------------------------------------------


def test_v2_checkpoint_serves_with_gen_zero(built_dot, tmp_path):
    index, centers, core, attrs, topic = built_dot
    ck = str(tmp_path / "v2")
    storage.save_index(index, ck, n_shards=2, layout=2)
    disk = DiskIVFIndex.open(ck)
    assert disk.man["layout"] == 2
    assert np.array_equal(disk.gens, np.zeros(KC, np.int64))
    assert int(disk.reader.read(0)["gen"][0]) == 0  # synthesized

    q = jnp.asarray(core[:8])
    fs = match_all(8, M)
    eng_d = SearchEngine(disk, k=K, n_probes=NP, q_block=QB)
    eng_r = SearchEngine(index, k=K, n_probes=NP, q_block=QB)
    _assert_results_equal(eng_d.search(q, fs), eng_r.search(q, fs), "v2")

    with pytest.raises(GenerationMismatchError):
        make_fused_search_fn(disk, k=K, n_probes=NP, delta_budget_mb=1.0)
    with pytest.raises(GenerationMismatchError):
        compact_deltas(ck)
    eng_d.close()
    eng_r.close()
    disk.close()


def test_check_complete_validates_gens(built_dot, tmp_path):
    index = built_dot[0]
    ck = str(tmp_path / "v3")
    storage.save_index(index, ck, n_shards=2)
    man = storage.load_manifest(ck)
    storage.check_complete(ck, man)  # intact: fine
    os.remove(os.path.join(ck, storage.GENS_FILE))
    with pytest.raises(FileNotFoundError):
        storage.check_complete(ck, man)
    with pytest.raises(GenerationMismatchError):
        storage.load_gens(ck, man)
    # shape mismatch (truncated vector) is the typed error too
    np.save(os.path.join(ck, storage.GENS_FILE),
            np.zeros(KC - 1, np.int64))
    with pytest.raises(GenerationMismatchError):
        storage.load_gens(ck, man)


def test_refresh_noop_without_republish(built_dot, tmp_path):
    index = built_dot[0]
    disk, tier = _open_live(index, str(tmp_path / "ck"))
    eng = SearchEngine(disk, k=K, n_probes=NP, q_block=QB)
    assert eng.refresh() is False  # nothing published → nothing to adopt
    eng.close()
    disk.close()


# ---------------------------------------------------------------------------
# Satellite: stale-summary accounting + compaction on the RAM tier
# ---------------------------------------------------------------------------


def test_stale_counts_and_compact_stale(built_dot):
    index, centers, core, attrs, topic = built_dot
    # tombstone 3 rows of cluster 1 and 2 rows of cluster 5
    cl = jnp.asarray([1, 1, 1, 5, 5])
    sl = jnp.asarray([0, 1, 2, 0, 1])
    tombed = update_lib.tombstone(index, cl, sl)
    sc = np.asarray(stale_counts(tombed))
    expect = np.zeros(KC, np.int32)
    expect[1], expect[5] = 3, 2
    np.testing.assert_array_equal(sc, expect)

    compacted, n = compact_stale(tombed, threshold=1)
    assert n == 2
    assert not np.asarray(stale_counts(compacted)).any()
    # compaction only reclaims slots + tightens summaries: results identical
    q = jnp.asarray(core[:8])
    fs = _window_fspec(8, 900)
    ea = SearchEngine(tombed, k=K, n_probes=NP, q_block=QB, prune="on")
    eb = SearchEngine(compacted, k=K, n_probes=NP, q_block=QB, prune="on")
    _assert_results_equal(ea.search(q, fs), eb.search(q, fs), "compacted")
    # and the tightened summaries prune at least as hard
    assert (np.asarray(eb.search(q, fs).n_scanned).sum()
            <= np.asarray(ea.search(q, fs).n_scanned).sum())
    ea.close()
    eb.close()


# ---------------------------------------------------------------------------
# Satellite: one flat metrics surface
# ---------------------------------------------------------------------------


def test_engine_metrics_flat(built_dot, tmp_path):
    index, centers, core, attrs, topic = built_dot
    disk, tier = _open_live(index, str(tmp_path / "ck"))
    eng = SearchEngine(disk, k=K, n_probes=NP, q_block=QB)
    tier.add(core[:2], attrs[:2].astype(np.int16),
             np.asarray([8000, 8001]))
    eng.search(jnp.asarray(core[:8]), match_all(8, M))
    m = eng.metrics()
    assert isinstance(m, dict)
    for key, val in m.items():
        assert isinstance(key, str) and "." in key, key
        assert isinstance(val, (bool, int, float, str, type(None))), (
            key, type(val))
    for prefix in ("engine.", "store.", "cache.", "delta."):
        assert any(k.startswith(prefix) for k in m), prefix
    assert m["engine.delta_folds"] >= 1
    assert m["delta.rows"] == 2
    eng.close()
    disk.close()


# ---------------------------------------------------------------------------
# Satellite: SQ8 delta rows (quantize="on") — ~4× capacity per budget,
# near-float parity live, and a dequantizing republish over a float cold
# tier
# ---------------------------------------------------------------------------


def test_delta_quantize_capacity_ratio(built_dot, tmp_path):
    """`for_index(quantize="on")` sizes rows at 1 byte/dim + 4-byte scale
    — the exact row-formula ratio over the float32 sizing (~3.5× at D=32,
    →4× as D grows)."""
    index, *_ = built_dot
    disk, _ = _open_live(index, str(tmp_path / "ck"))
    t_f = DeltaTier.for_index(disk, 8.0)
    t_q = DeltaTier.for_index(disk, 8.0, quantize="on")
    row_f = D * 4 + M * 2 + 8
    row_q = D * 1 + M * 2 + 8 + 4
    assert t_f.capacity == (8 * 2 ** 20) // row_f
    assert t_q.capacity == (8 * 2 ** 20) // row_q
    assert t_q.capacity * row_f >= t_f.capacity * row_q  # strictly denser
    assert t_q.quantized and not t_f.quantized
    disk.close()


def test_delta_quantize_on_near_float_parity(built_dot, tmp_path):
    """Quantized delta rows over a FLOAT cold tier: ids match a float
    delta tier's results almost everywhere and scores agree to SQ8
    precision (≈1e-2 relative)."""
    index, centers, core, attrs, topic = built_dot
    rng = np.random.default_rng(23)
    add = (centers[rng.integers(0, KC, 64)]
           + 0.05 * rng.standard_normal((64, D))).astype(np.float32)
    add /= np.linalg.norm(add, axis=-1, keepdims=True)
    add_attrs = rng.integers(0, TS_RANGE, (64, M)).astype(np.int16)
    new_ids = np.arange(N, N + 64)

    results = {}
    for mode in ("auto", "on"):
        ck = str(tmp_path / f"ck_{mode}")
        storage.save_index(index, ck, n_shards=2)
        disk = DiskIVFIndex.open(ck)
        tier = DeltaTier.for_index(disk, 8.0, quantize=mode)
        disk.delta = tier
        tier.add(add, add_attrs, new_ids)
        tier.tombstone(new_ids[:5])
        eng = SearchEngine(disk, k=K, n_probes=NP, q_block=QB)
        q = jnp.asarray(add[5:21] + 0.001)
        results[mode] = eng.search(q, match_all(16, M))
        eng.close()
        disk.close()

    ids_f = np.asarray(results["auto"].ids)
    ids_q = np.asarray(results["on"].ids)
    agree = np.mean(ids_f == ids_q)
    assert agree >= 0.9, f"id agreement {agree}"
    np.testing.assert_allclose(np.asarray(results["on"].scores),
                               np.asarray(results["auto"].scores),
                               rtol=2e-2, atol=2e-2)
    # none of the tombstoned delta rows surfaced
    assert not np.isin(ids_q, new_ids[:5]).any()


def test_delta_quantize_republish_dequantizes(built_dot, tmp_path):
    """compact_deltas over a float cold tier folds quantized delta rows by
    DEQUANTIZING codes·scales — the checkpoint stays float (no manifest
    flip) and post-republish results match the live pre-republish view."""
    index, centers, core, attrs, topic = built_dot
    ck = str(tmp_path / "ck")
    disk, tier = _open_live(index, ck)
    tier2 = DeltaTier.for_index(disk, 8.0, quantize="on")
    disk.delta = tier2

    rng = np.random.default_rng(29)
    add = (centers[rng.integers(0, KC, 48)]
           + 0.05 * rng.standard_normal((48, D))).astype(np.float32)
    add /= np.linalg.norm(add, axis=-1, keepdims=True)
    add_attrs = rng.integers(0, TS_RANGE, (48, M)).astype(np.int16)
    new_ids = np.arange(N, N + 48)
    tier2.add(add, add_attrs, new_ids)

    eng = SearchEngine(disk, k=K, n_probes=NP, q_block=QB)
    q = jnp.asarray(add[:16] + 0.001)
    fs = match_all(16, M)
    before = eng.search(q, fs)

    st = compact_deltas(ck, tier2)
    assert st.rows_folded == 48
    assert eng.refresh()
    assert tier2.stats()["rows"] == 0
    man = storage.load_manifest(ck)
    assert not man.get("quantized", False)  # cold tier still float

    after = eng.search(q, fs)
    np.testing.assert_array_equal(np.asarray(after.ids),
                                  np.asarray(before.ids))
    np.testing.assert_allclose(np.asarray(after.scores),
                               np.asarray(before.scores),
                               rtol=1e-4, atol=1e-4)
    eng.close()
    disk.close()


def test_metrics_text_stage_latency_histograms(built_dot, tmp_path):
    """Satellite: fixed-bucket Prometheus latency histograms per pipeline
    stage — plan/fetch/scan_dispatch/merge_dispatch/delta_fold (the
    pipelined executor's scan and merge timers end at dispatch) — with
    classic cumulative ``le`` semantics and matching ``_count``/``_sum``
    rows."""
    index, centers, core, attrs, topic = built_dot
    disk, tier = _open_live(index, str(tmp_path / "ck"))
    tier.add(core[:4], attrs[:4].astype(np.int16),
             np.arange(8000, 8004))
    # pipelined executor: the per-tile fetch/scan overlap plus a distinct
    # merge stage, so all five stage histograms populate
    eng = SearchEngine(disk, k=K, n_probes=NP, q_block=QB, pipeline="on")
    for _ in range(3):  # q=21 → 3 tiles: the merge stage actually runs
        eng.search(jnp.asarray(core[:21]), match_all(21, M))
    text = eng.metrics_text()
    assert "# TYPE repro_stage_latency_seconds histogram" in text
    for stage in ("plan", "fetch", "scan_dispatch", "merge_dispatch",
                  "delta_fold"):
        bucket_counts = []
        for line in text.splitlines():
            if (line.startswith("repro_stage_latency_seconds_bucket")
                    and f'stage="{stage}"' in line):
                bucket_counts.append(int(line.rsplit(" ", 1)[1]))
        assert bucket_counts, f"no buckets for stage {stage}"
        # fixed bucket set, cumulative and non-decreasing
        assert bucket_counts == sorted(bucket_counts), (stage, bucket_counts)
        count_line = next(
            line for line in text.splitlines()
            if line.startswith("repro_stage_latency_seconds_count")
            and f'stage="{stage}"' in line
        )
        total = int(count_line.rsplit(" ", 1)[1])
        assert total >= 3 and bucket_counts[-1] <= total
        sum_line = next(
            line for line in text.splitlines()
            if line.startswith("repro_stage_latency_seconds_sum")
            and f'stage="{stage}"' in line
        )
        assert float(sum_line.rsplit(" ", 1)[1]) >= 0.0
    # the fixed edges render with le labels (first + implicit ordering)
    assert 'le="0.0005"' in text and 'le="2.5"' in text
    eng.close()
    disk.close()
