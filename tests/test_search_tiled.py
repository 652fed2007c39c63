"""Tiled, probe-deduplicated fused search: plan unit tests + parity matrix.

Parity bar: `search_fused_tiled` must return IDENTICAL ids/scores to
`search_reference` (continuous random scores ⇒ no meaningful ties) across
metrics, SQ8 on/off, selective vs match-all filters, ragged query tiles and
both executors ("xla" streaming, "pallas_interpret" kernel).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    FilterBuilder,
    HybridSpec,
    brute_force,
    build_ivf,
    from_builders,
    match_all,
    recall_at_k,
)
from repro.core.engine import SearchEngine
from repro.core.ivf import quantize_index
from repro.core.probes import dedup_rows, plan_probe_tiles
from repro.core.search import search_centroids, search_reference
from repro.core.serving import make_fused_search_fn
from repro.kernels.filtered_scan import (
    filtered_scan_tiled,
    filtered_scan_tiled_ref,
    search_fused_tiled,
)
from repro.kernels.filtered_scan.filtered_scan import fold_steps

BACKENDS = ("xla", "pallas_interpret")


# ---------------------------------------------------------------------------
# probe-plan unit tests
# ---------------------------------------------------------------------------


def test_dedup_rows_basic():
    keys = jnp.asarray([[3, 1, 3, 1, 7, 7], [5, 5, 5, 5, 5, 5]], jnp.int32)
    table, slot_of, count = dedup_rows(keys, None, cap=4)
    np.testing.assert_array_equal(np.asarray(count), [3, 1])
    # ascending uniques, tail padded with the last unique key
    np.testing.assert_array_equal(np.asarray(table[0]), [1, 3, 7, 7])
    np.testing.assert_array_equal(np.asarray(table[1]), [5, 5, 5, 5])
    # every entry's slot points at its own key
    t, s = np.asarray(table), np.asarray(slot_of)
    for r in range(2):
        np.testing.assert_array_equal(
            t[r][s[r]], np.asarray(keys[r])
        )


def test_dedup_rows_invalid_and_empty():
    keys = jnp.asarray([[9, 2, 9, 4], [1, 1, 1, 1]], jnp.int32)
    valid = jnp.asarray([[True, False, True, True], [False] * 4])
    table, slot_of, count = dedup_rows(keys, valid, cap=4)
    np.testing.assert_array_equal(np.asarray(count), [2, 0])
    np.testing.assert_array_equal(np.asarray(table[0]), [4, 9, 9, 9])
    np.testing.assert_array_equal(np.asarray(table[1]), [0, 0, 0, 0])
    # valid entries map to their key; slot indices stay in range either way
    assert int(table[0, slot_of[0, 0]]) == 9
    assert int(table[0, slot_of[0, 3]]) == 4
    assert np.asarray(slot_of).max() < 4 and np.asarray(slot_of).min() >= 0


def test_plan_probe_tiles_streams_each_cluster_once():
    """The acceptance property: per tile, every probed cluster gets exactly
    one live slot, however many queries probe it."""
    rng = np.random.default_rng(0)
    q_block, t, kc = 8, 4, 6
    probe_ids = jnp.asarray(rng.integers(0, kc, (16, t)), jnp.int32)
    u_cap = min(q_block * t, kc)
    slot_cluster, slot_tile, slot_of_probe, probe_ok, n_unique = (
        plan_probe_tiles(probe_ids, q_block=q_block, u_cap=u_cap)
    )
    assert np.asarray(probe_ok).all()  # u_cap=min(QB·T, K) never overflows
    sc = np.asarray(slot_cluster).reshape(2, u_cap)
    for tile in range(2):
        probed = np.unique(np.asarray(probe_ids[tile * 8:(tile + 1) * 8]))
        n = int(n_unique[tile])
        assert n == len(probed)  # deduped: one slot per distinct cluster
        np.testing.assert_array_equal(np.sort(sc[tile][:n]), probed)
        # pads repeat the last unique id (Pallas revisiting fast path)
        assert (sc[tile][n:] == sc[tile][n - 1]).all()
    # every probe's slot scans that probe's cluster, in the right tile
    sc_flat = np.asarray(slot_cluster)
    st_flat = np.asarray(slot_tile)
    sop = np.asarray(slot_of_probe)
    for qi in range(16):
        for ti in range(t):
            assert sc_flat[sop[qi, ti]] == int(probe_ids[qi, ti])
            assert st_flat[sop[qi, ti]] == qi // q_block


# ---------------------------------------------------------------------------
# kernel vs gather oracle
# ---------------------------------------------------------------------------


def _tiled_case(seed, *, s, n_tiles, q_block, kc, vpad, d, m, f):
    rng = np.random.default_rng(seed)
    return dict(
        slot_cluster=jnp.asarray(rng.integers(0, kc, s), jnp.int32),
        slot_tile=jnp.asarray(rng.integers(0, n_tiles, s), jnp.int32),
        queries=jnp.asarray(
            rng.standard_normal((n_tiles * q_block, d)).astype(np.float32)
        ),
        lo=jnp.asarray(
            rng.integers(-20, 5, (n_tiles * q_block, f, m)), jnp.int16
        ),
        hi=jnp.asarray(
            rng.integers(5, 30, (n_tiles * q_block, f, m)), jnp.int16
        ),
        vectors=jnp.asarray(
            rng.standard_normal((kc, vpad, d)).astype(np.float32)
        ),
        attrs=jnp.asarray(rng.integers(-25, 25, (kc, vpad, m)), jnp.int16),
        ids=jnp.asarray(rng.integers(-1, 60, (kc, vpad)), jnp.int32),
    )


@pytest.mark.parametrize("metric", ["dot", "l2"])
def test_tiled_kernel_matches_ref(metric):
    c = _tiled_case(3, s=5, n_tiles=2, q_block=8, kc=4, vpad=256, d=32,
                    m=4, f=2)
    norms = jnp.sum(c["vectors"].astype(jnp.float32) ** 2, -1)
    args = (c["slot_cluster"], c["slot_tile"], c["queries"], c["lo"],
            c["hi"], c["vectors"], c["attrs"], c["ids"],
            norms if metric == "l2" else None)
    kw = dict(metric=metric, k=7, q_block=8)
    vals, ids, npass, _ = filtered_scan_tiled(*args, interpret=True,
                                              v_block=128, **kw)
    rvals, rids, rnpass = filtered_scan_tiled_ref(*args, **kw)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(rvals),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(rids))
    np.testing.assert_array_equal(np.asarray(npass), np.asarray(rnpass))


def test_tiled_kernel_sq8_matches_ref():
    c = _tiled_case(4, s=4, n_tiles=1, q_block=8, kc=3, vpad=128, d=16,
                    m=3, f=1)
    v32 = c["vectors"].astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(v32), -1), 1e-12) / 127.0
    q8 = jnp.clip(jnp.round(v32 / scale[..., None]), -127, 127).astype(
        jnp.int8
    )
    args = (c["slot_cluster"], c["slot_tile"], c["queries"], c["lo"],
            c["hi"], q8, c["attrs"], c["ids"], None, scale)
    kw = dict(metric="dot", k=5, q_block=8)
    vals, ids, npass, _ = filtered_scan_tiled(*args, interpret=True,
                                              v_block=64, **kw)
    rvals, rids, rnpass = filtered_scan_tiled_ref(*args, **kw)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(rvals),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(rids))
    np.testing.assert_array_equal(np.asarray(npass), np.asarray(rnpass))


# The top-k fold inserts only block entries above a query's running kth.
# Integer rows and queries make every score exact, so ties are common and
# the kernel must equal the reference bit for bit, order under ties
# included.  Lists: 0 is the case's own layout; 1 is dead; 2 has a dead
# block and a partly dead one; 3 repeats its first block in every block,
# so later blocks tie the running set, at the kth too.
FOLD_QB, FOLD_VPAD, FOLD_D, FOLD_M = 8, 512, 16, 2
FOLD_SLOTS = ((0, 0), (1, 0), (2, 1), (3, 1), (0, 1))  # (list, tile)
FOLD_CASES = {
    # name: (list 0 layout, metric, SQ8, k, v_block, value range, filter)
    "ties-in-block-k100-vb128": ("random", "dot", False, 100, 128, 2, "wide"),
    "ties-across-blocks-k100-vb256": ("repeat", "dot", False, 100, 256, 3,
                                      "wide"),
    "ties-at-kth-k13-vb128": ("repeat", "dot", False, 13, 128, 2, "wide"),
    "ascending-k100-vb128": ("ascending", "dot", False, 100, 128, 2, "wide"),
    "ascending-k100-vb256": ("ascending", "dot", False, 100, 256, 2, "wide"),
    "ascending-k13-vb128": ("ascending", "dot", False, 13, 128, 2, "wide"),
    "dead-blocks-k10-vb128": ("dead", "dot", False, 10, 128, 4, "wide"),
    "fewer-than-k-k100-vb256": ("random", "dot", False, 100, 256, 4,
                                "narrow"),
    "l2-k13-vb128": ("random", "l2", False, 13, 128, 2, "wide"),
    "l2-ascending-k100-vb256": ("ascending", "l2", False, 100, 256, 2,
                                "wide"),
    "sq8-k100-vb128": ("random", "dot", True, 100, 128, 2, "wide"),
    "sq8-repeat-k13-vb256": ("repeat", "dot", True, 13, 256, 3, "narrow"),
}


def _fold_case(layout, metric, sq8, span, filt):
    rng = np.random.default_rng(11)
    kc, n_q = 4, 2 * FOLD_QB
    vec = rng.integers(-span, span + 1, (kc, FOLD_VPAD, FOLD_D))
    q = rng.integers(-span, span + 1, (n_q, FOLD_D))
    attrs = rng.integers(-6, 7, (kc, FOLD_VPAD, FOLD_M))
    ids = rng.permutation(kc * FOLD_VPAD).reshape(kc, FOLD_VPAD)
    ids = np.where(rng.random((kc, FOLD_VPAD)) < 0.1, -1, ids)
    if layout == "ascending":  # every query's scores rise along list 0
        # dot: q₀·v₀ with q₀ > 0 and v₀ rising; l2: v₀ < 0 rising towards
        # every q₀ > 0, so ‖q − v‖ falls
        vec[0] = 0
        vec[0, :, 0] = np.arange(1, FOLD_VPAD + 1) - (
            FOLD_VPAD + 1 if metric == "l2" else 0)
        q[:, 0] = np.abs(q[:, 0]) + 1
        attrs[0] = 0
        ids[0] = np.arange(FOLD_VPAD)
    elif layout == "dead":  # list 0 dead but for its last block
        ids[0, :-128] = -1
    elif layout == "repeat":
        vec[0] = np.tile(vec[0, :128], (FOLD_VPAD // 128, 1))
        attrs[0] = np.tile(attrs[0, :128], (FOLD_VPAD // 128, 1))
    ids[1] = -1
    ids[2, 128:256] = -1
    ids[2, 256:384:3] = -1
    vec[3] = np.tile(vec[3, :128], (FOLD_VPAD // 128, 1))
    attrs[3] = np.tile(attrs[3, :128], (FOLD_VPAD // 128, 1))
    if filt == "wide":
        lo = rng.integers(-6, 1, (n_q, 1, FOLD_M))
        hi = rng.integers(0, 7, (n_q, 1, FOLD_M))
    else:  # a few rows per list pass
        lo = rng.integers(-6, 7, (n_q, 1, FOLD_M))
        hi = lo + 1
    vectors = vec.astype(np.float32)
    norms = scales = None
    if metric == "l2":
        norms = jnp.asarray(np.sum(vectors ** 2, -1))
    if sq8:
        scales = jnp.asarray(np.full((kc, FOLD_VPAD), 0.25, np.float32))
        vectors = vectors.astype(np.int8)
    sc, st = (jnp.asarray(c, jnp.int32) for c in zip(*FOLD_SLOTS))
    return (sc, st, jnp.asarray(q.astype(np.float32)),
            jnp.asarray(lo, jnp.int16), jnp.asarray(hi, jnp.int16),
            jnp.asarray(vectors), jnp.asarray(attrs, jnp.int16),
            jnp.asarray(ids, jnp.int32), norms, scales)


def _fold_passes_model(args, *, metric, k, v_block):
    """Per slot: for each row block, the most block scores any query of the
    tile has strictly above its running kth, at most k, summed."""
    sc, st, q, lo, hi, vectors, attrs, ids, norms, scales = (
        None if a is None else np.asarray(a) for a in args)
    out = []
    for c, t in zip(sc, st):
        qq = q[t * FOLD_QB:(t + 1) * FOLD_QB]
        s = (qq @ vectors[c].astype(np.float32).T).astype(np.float32)
        if scales is not None:
            s = s * scales[c][None]
        if metric == "l2":
            s = np.float32(2.0) * s - norms[c][None]
        a = attrs[c][None]
        ok = np.all((a >= lo[t * FOLD_QB:(t + 1) * FOLD_QB, 0][:, None])
                    & (a <= hi[t * FOLD_QB:(t + 1) * FOLD_QB, 0][:, None]),
                    -1) & (ids[c] >= 0)[None]
        s = np.where(ok, s, -np.inf)
        passes = 0
        for b0 in range(0, FOLD_VPAD, v_block):
            seen = np.sort(s[:, :b0], axis=1)[:, ::-1]
            kth = (seen[:, k - 1] if b0 >= k
                   else np.full(FOLD_QB, -np.inf))
            above = np.sum(s[:, b0:b0 + v_block] > kth[:, None], axis=1)
            passes += int(np.minimum(above, k).max())
        out.append(passes)
    return np.asarray(out)


@pytest.mark.parametrize("case", sorted(FOLD_CASES))
def test_tiled_fold_matches_ref(case):
    layout, metric, sq8, k, v_block, span, filt = FOLD_CASES[case]
    args = _fold_case(layout, metric, sq8, span, filt)
    kw = dict(metric=metric, k=k, q_block=FOLD_QB)
    vals, ids, npass, nfold = filtered_scan_tiled(
        *args, interpret=True, v_block=v_block, **kw)
    rvals, rids, rnpass = filtered_scan_tiled_ref(*args, **kw)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(rvals))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(rids))
    np.testing.assert_array_equal(np.asarray(npass), np.asarray(rnpass))

    nfold = np.asarray(nfold)
    steps = FOLD_VPAD // v_block
    np.testing.assert_array_equal(
        nfold, _fold_passes_model(args, metric=metric, k=k, v_block=v_block))
    assert (nfold <= steps * k).all()  # at most k passes per grid step
    assert nfold[1] == 0  # a dead list needs no pass
    if layout == "ascending":  # every block beats every kth: k per block
        assert nfold[0] == nfold[4] == steps * k
    if filt == "narrow":  # fewer rows pass than k: NEG_INF / -1 pads
        npass = np.asarray(npass)
        assert (npass < k).any()
        pad = np.arange(k) >= npass[..., None]
        assert (np.asarray(ids)[pad] == -1).all()
        assert (np.asarray(vals)[pad] == -3.0e38).all()


# ---------------------------------------------------------------------------
# end-to-end parity matrix
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module", params=["dot", "l2"])
def built(request):
    metric = request.param
    rng = np.random.default_rng(0)
    n, d, m = 1536, 32, 6
    core = rng.standard_normal((n, d)).astype(np.float32)
    core /= np.linalg.norm(core, axis=-1, keepdims=True)
    attrs = rng.integers(0, 10, (n, m)).astype(np.int16)
    spec = HybridSpec(dim=d, n_attrs=m, core_dtype=jnp.float32,
                      metric=metric)
    index, _ = build_ivf(
        jax.random.key(0), spec, core, attrs, n_clusters=10,
        kmeans_mode="lloyd", kmeans_steps=6,
    )
    return index, core, attrs


def _fspecs(q, m):
    selective = from_builders(
        [FilterBuilder(m).le(0, 5).ge(1, 2) for _ in range(q)]
    )
    return {"match_all": match_all(q, m), "selective": selective}


# Q values chosen to exercise ragged tiles: 5 (sub-tile), 21 (ragged
# multi-tile), 32 (exact tiles) at q_block=16.
@pytest.mark.parametrize("q", [5, 21, 32])
@pytest.mark.parametrize("backend", BACKENDS)
def test_tiled_matches_reference(built, q, backend):
    index, core, attrs = built
    queries = jnp.asarray(core[7:7 + q] + 0.01)
    for name, fspec in _fspecs(q, 6).items():
        ref = search_reference(index, queries, fspec, k=10, n_probes=4)
        tiled = search_fused_tiled(
            index, queries, fspec, k=10, n_probes=4, q_block=16,
            v_block=128, backend=backend,
        )
        np.testing.assert_array_equal(
            np.asarray(tiled.ids), np.asarray(ref.ids), err_msg=name
        )
        np.testing.assert_allclose(
            np.asarray(tiled.scores), np.asarray(ref.scores),
            rtol=1e-5, atol=1e-5, err_msg=name,
        )
        np.testing.assert_array_equal(
            np.asarray(tiled.n_passed), np.asarray(ref.n_passed),
            err_msg=name,
        )
        np.testing.assert_array_equal(
            np.asarray(tiled.n_scanned), np.asarray(ref.n_scanned),
            err_msg=name,
        )


@pytest.mark.parametrize("backend", BACKENDS)
def test_tiled_sq8_matches_reference(built, backend):
    index, core, attrs = built
    if index.spec.metric == "l2":
        pytest.skip("SQ8 + l2 not wired (matches non-tiled kernel)")
    qindex = quantize_index(index)
    q = 12
    queries = jnp.asarray(core[:q])
    fspec = match_all(q, 6)
    ref = search_reference(qindex, queries, fspec, k=8, n_probes=4)
    tiled = search_fused_tiled(qindex, queries, fspec, k=8, n_probes=4,
                               q_block=8, v_block=128, backend=backend)
    np.testing.assert_array_equal(np.asarray(tiled.ids), np.asarray(ref.ids))
    np.testing.assert_allclose(np.asarray(tiled.scores),
                               np.asarray(ref.scores), rtol=1e-5, atol=1e-5)


def test_tiled_full_probe_matches_brute_force(built):
    index, core, attrs = built
    q = 9
    queries = jnp.asarray(core[40:40 + q])
    fspec = match_all(q, 6)
    oracle = brute_force(jnp.asarray(core), jnp.asarray(attrs), queries,
                         fspec, k=8, metric=index.spec.metric)
    tiled = search_fused_tiled(index, queries, fspec, k=8,
                               n_probes=index.n_clusters, q_block=8,
                               v_block=128, backend="xla")
    np.testing.assert_array_equal(np.asarray(tiled.ids),
                                  np.asarray(oracle.ids))
    assert recall_at_k(tiled, oracle) == 1.0


def test_tiled_shares_duplicate_probes(built):
    """Batch of identical queries ⇒ one tile's slot table collapses to T
    unique slots (each duplicate cluster streamed once), results intact."""
    index, core, attrs = built
    q, t = 16, 4
    queries = jnp.broadcast_to(jnp.asarray(core[3]), (q, 32))
    probe_ids, _ = search_centroids(index, queries, t)
    _, _, _, _, n_unique = plan_probe_tiles(
        jnp.asarray(probe_ids), q_block=16, u_cap=min(16 * t, 10)
    )
    assert int(n_unique[0]) == t  # Q·T = 64 probes → T unique slots
    fspec = match_all(q, 6)
    ref = search_reference(index, queries, fspec, k=6, n_probes=t)
    tiled = search_fused_tiled(index, queries, fspec, k=6, n_probes=t,
                               q_block=16, backend="xla")
    np.testing.assert_array_equal(np.asarray(tiled.ids), np.asarray(ref.ids))


def test_tiled_undersized_u_cap_degrades_soundly(built):
    """u_cap below the tile's unique-probe count must DROP probes (counted
    candidates shrink) — never surface wrong ids or fabricated scores."""
    index, core, attrs = built
    if index.spec.metric == "l2":
        pytest.skip("score spot-check below is written for dot")
    q = 16
    queries = jnp.asarray(core[:q] + 0.01)
    fspec = match_all(q, 6)
    ref = search_reference(index, queries, fspec, k=6, n_probes=4)
    small = search_fused_tiled(index, queries, fspec, k=6, n_probes=4,
                               q_block=16, u_cap=4, backend="xla")
    ids_ = np.asarray(small.ids)
    scores_ = np.asarray(small.scores)
    qn = np.asarray(queries)
    for qi in range(q):
        for j in range(6):
            vid = ids_[qi, j]
            if vid >= 0:  # every surfaced hit is a real (query, vector) score
                np.testing.assert_allclose(
                    scores_[qi, j], float(qn[qi] @ core[vid]),
                    rtol=1e-4, atol=1e-4,
                )
    assert (ids_ >= 0).sum() <= (np.asarray(ref.ids) >= 0).sum()
    assert (np.asarray(small.n_passed) <= np.asarray(ref.n_passed)).all()


def test_serving_search_fn_uses_tiled_path(built):
    index, core, attrs = built
    fn = make_fused_search_fn(index, k=5, n_probes=4, q_block=8)
    q = 8
    queries = jnp.asarray(core[:q])
    scores, ids = fn(queries, match_all(q, 6), None)
    ref = search_reference(index, queries, match_all(q, 6), k=5, n_probes=4)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ref.ids))


@pytest.mark.parametrize("executor", [
    dict(pipeline="off"), dict(pipeline="on"),
    dict(pipeline="off", termination="exact"),
], ids=["sync", "pipelined", "terminated"])
def test_engine_counts_fold_passes(built, executor):
    """Every executor that runs the kernel counts its insertion passes
    against k per grid step; the XLA executor has no fold and counts none."""
    index, core, attrs = built
    q, k = 21, 10
    queries = jnp.asarray(core[:q] + 0.01)
    kw = dict(k=k, n_probes=4, q_block=16, v_block=128, u_cap=10,
              **executor)
    kernel = SearchEngine(index, backend="pallas_interpret", **kw)
    xla = SearchEngine(index, backend="xla", **kw)
    want = search_reference(index, queries, match_all(q, 6), k=k,
                            n_probes=4)
    for eng in (kernel, xla):
        res = eng.search(queries, match_all(q, 6))
        np.testing.assert_array_equal(np.asarray(res.ids),
                                      np.asarray(want.ids))
    m = kernel.metrics()
    steps = fold_steps(int(index.vectors.shape[1]), 128)
    slots = 2 * 10  # two query tiles × u_cap
    if "termination" in executor:  # the scanned segments' padded slots
        assert m["engine.fold_k_passes"] > 0
        assert m["engine.fold_k_passes"] % (steps * k) == 0
    else:
        assert m["engine.fold_k_passes"] == slots * steps * k
    assert 0 < m["engine.fold_passes"] <= m["engine.fold_k_passes"]
    assert 'repro_engine_fold_passes ' in kernel.metrics_text()
    mx = xla.metrics()
    assert mx["engine.fold_passes"] == mx["engine.fold_k_passes"] == 0
    kernel.close()
    xla.close()
