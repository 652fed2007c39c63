"""Persistence round-trips, elastic resharding, and the serving loop."""

import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    HybridSpec,
    build_ivf,
    match_all,
    search_reference,
)
from repro.core import storage
from repro.core.serving import SearchServer, ShardHealth


@pytest.fixture(scope="module")
def built():
    rng = np.random.default_rng(0)
    n, d, m = 600, 12, 3
    core = rng.standard_normal((n, d)).astype(np.float32)
    core /= np.linalg.norm(core, axis=-1, keepdims=True)  # dot == cosine
    attrs = rng.integers(0, 5, (n, m)).astype(np.int16)
    spec = HybridSpec(dim=d, n_attrs=m, core_dtype=jnp.float32)
    index, _ = build_ivf(
        jax.random.key(0), spec, core, attrs, n_clusters=6,
        kmeans_mode="lloyd", kmeans_steps=4,
    )
    return index, core, attrs


def _same_results(a, b, queries, k=8):
    fspec = match_all(queries.shape[0], a.spec.n_attrs)
    ra = search_reference(a, queries, fspec, k=k, n_probes=a.n_clusters)
    rb = search_reference(b, queries, fspec, k=k, n_probes=b.n_clusters)
    np.testing.assert_array_equal(np.asarray(ra.ids), np.asarray(rb.ids))


def test_save_load_roundtrip(built, tmp_path):
    index, core, _ = built
    storage.save_index(index, str(tmp_path / "idx"), n_shards=3)
    loaded = storage.load_index(str(tmp_path / "idx"))
    assert loaded.n_clusters == index.n_clusters
    np.testing.assert_array_equal(
        np.asarray(loaded.counts), np.asarray(index.counts)
    )
    _same_results(index, loaded, jnp.asarray(core[:5]))


def test_elastic_reshard(built, tmp_path):
    """Save from 3 'chips', restore for 4 — K padded, results identical."""
    index, core, _ = built
    storage.save_index(index, str(tmp_path / "idx2"), n_shards=3)
    loaded = storage.load_index(str(tmp_path / "idx2"), target_shards=4)
    assert loaded.n_clusters % 4 == 0
    assert loaded.n_clusters >= index.n_clusters
    _same_results(index, loaded, jnp.asarray(core[:5]))


def test_incomplete_checkpoint_rejected(built, tmp_path):
    import os

    index, _, _ = built
    d = str(tmp_path / "idx3")
    storage.save_index(index, d, n_shards=3)
    os.unlink(storage.shard_paths(d, storage.load_manifest(d))[1])
    with pytest.raises(FileNotFoundError):
        storage.load_index(d)


def test_shard_health_probation():
    h = ShardHealth(4, threshold=0.15, decay=0.5)
    assert h.ok_mask().all()
    h.report(2, failed=True)
    h.report(2, failed=True)
    assert not h.ok_mask()[2] and h.ok_mask()[[0, 1, 3]].all()
    for _ in range(6):
        h.report(2, failed=False)
    assert h.ok_mask().all()  # probation ends


@pytest.mark.slow
def test_serving_loop_end_to_end(built):
    index, core, attrs = built
    k = 5

    def search_fn(queries, fspec, shard_ok):
        del shard_ok
        res = search_reference(index, queries, fspec, k=k, n_probes=4)
        return res.scores, res.ids

    server = SearchServer(
        search_fn, batch_size=8, dim=12, n_attrs=3, n_terms=1, n_shards=4,
        max_wait_s=0.01,
    )
    server.start()
    try:
        futs = [server.submit(core[i]) for i in range(20)]
        resps = [f.get(timeout=60) for f in futs]
    finally:
        server.stop()
    assert len(resps) == 20
    for i, r in enumerate(resps):
        assert r.ids.shape == (k,)
        assert r.ids[0] == i  # nearest neighbor of a db vector is itself
        assert not r.degraded
    assert server.stats["requests"] == 20
    assert server.stats["batches"] >= 3  # micro-batching actually batched


def test_serving_loop_failed_batch_reaches_caller():
    """An exception from search_fn is raised at every waiting caller (not a
    queue.Empty at the timeout), and the loop keeps serving later batches."""
    failing = [True]

    def search_fn(queries, fspec, shard_ok):
        del fspec, shard_ok
        if failing[0]:
            raise RuntimeError("scan failed on the device")
        q = queries.shape[0]
        return np.zeros((q, 2), np.float32), np.zeros((q, 2), np.int32)

    server = SearchServer(
        search_fn, batch_size=4, dim=3, n_attrs=2, n_terms=1, n_shards=1,
        max_wait_s=0.01,
    )
    server.start()
    try:
        futs = [server.submit(np.zeros(3, np.float32)) for _ in range(4)]
        t0 = time.monotonic()
        for f in futs:
            with pytest.raises(RuntimeError, match="scan failed"):
                f.get(timeout=30)
        assert time.monotonic() - t0 < 10
        failing[0] = False
        again = server.search_blocking(np.zeros(3, np.float32), timeout=30)
    finally:
        server.stop()
    assert again.ids.shape == (2,)
    assert server.stats["failed_batches"] >= 1
    assert not server._worker.is_alive()
