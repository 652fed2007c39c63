"""Multi-device distributed-search selftest (run in a subprocess with 8 fake
devices so the main pytest process keeps a single device).

Checks, on a (data=2, model=4) mesh:
  1. sharded search == single-device reference (ids + scores);
  2. straggler drop (shard_ok=False on one chip) yields a valid subset —
     every returned id still satisfies the filter and appears in the
     reference candidate set, and healthy-shard results are unchanged;
  3. dispatch overflow is counted when P_cap is forced tiny.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import AxisType  # noqa: E402

from repro.core import (  # noqa: E402
    FilterBuilder,
    HybridSpec,
    build_ivf,
    from_builders,
    match_all,
)
from repro.core.distributed import (  # noqa: E402
    ShardedSearchConfig,
    dispatch_probes,
    make_sharded_search,
    probe_capacity,
)
from repro.core.search import search_reference  # noqa: E402


def main():
    assert len(jax.devices()) == 8, jax.devices()
    rng = np.random.default_rng(0)
    n, d, m, kc = 4096, 32, 4, 16
    core = rng.standard_normal((n, d)).astype(np.float32)
    core /= np.linalg.norm(core, axis=-1, keepdims=True)
    attrs = rng.integers(0, 8, (n, m)).astype(np.int16)
    spec = HybridSpec(dim=d, n_attrs=m, core_dtype=jnp.float32)
    index, stats = build_ivf(
        jax.random.key(0), spec, core, attrs, n_clusters=kc,
        kmeans_mode="lloyd", kmeans_steps=5,
    )
    assert stats.n_dropped == 0

    mesh = jax.make_mesh(
        (2, 4), ("data", "model"), axis_types=(AxisType.Auto, AxisType.Auto)
    )
    q = 16
    cfg = ShardedSearchConfig(k=20, n_probes=4, v_block=128)
    search_fn, shardings, info = make_sharded_search(
        mesh, "dot", q_total=q, n_clusters=kc, cfg=cfg,
    )
    assert info["n_shards"] == 8 and info["k_local"] == 2

    # place index shards
    import dataclasses
    index = dataclasses.replace(
        index,
        centroids=jax.device_put(index.centroids, shardings["centroids"]),
        vectors=jax.device_put(index.vectors, shardings["vectors"]),
        attrs=jax.device_put(index.attrs, shardings["attrs"]),
        ids=jax.device_put(index.ids, shardings["ids"]),
        counts=jax.device_put(index.counts, shardings["counts"]),
    )

    queries = jnp.asarray(core[:q] + 0.01 * rng.standard_normal((q, d)).astype(np.float32))
    builders = [FilterBuilder(m).le(0, 5).ge(1, 1) for _ in range(q)]
    fspec = from_builders(builders)

    res = search_fn(index, queries, fspec)
    ref = search_reference(index, queries, fspec, k=20, n_probes=4)
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ref.ids))
    live = np.asarray(ref.scores) > -1e38
    np.testing.assert_allclose(
        np.asarray(res.scores)[live], np.asarray(ref.scores)[live],
        rtol=1e-5, atol=1e-5,
    )
    print("OK distributed == reference")

    # ---- tiled backend: per-shard probe dedup + streaming top-k ----
    cfg_tiled = ShardedSearchConfig(
        k=20, n_probes=4, v_block=128, scan_q_block=8, backend="xla_tiled",
    )
    search_fn_t, _, info_t = make_sharded_search(
        mesh, "dot", q_total=q, n_clusters=kc, cfg=cfg_tiled,
    )
    res_t = search_fn_t(index, queries, fspec)
    np.testing.assert_array_equal(np.asarray(res_t.ids), np.asarray(ref.ids))
    np.testing.assert_allclose(
        np.asarray(res_t.scores)[live], np.asarray(ref.scores)[live],
        rtol=1e-5, atol=1e-5,
    )
    print("OK tiled distributed == reference")

    # ---- filter-aware pruned dispatch: shards skip filtered-out clusters --
    # Topic-mixture index with a topic-correlated attr0 "timestamp" (one
    # cluster per topic, narrow per-topic band): a selective window filter
    # provably excludes most probed clusters, so the summary mask threaded
    # through dispatch_probes_tiled must actually drop probes — and ids must
    # stay bit-identical to both the unpruned dispatch and the reference.
    from repro.core.ivf import build_from_assignments
    from repro.core.summaries import can_match
    from repro.core.filters import FilterSpec

    centers = rng.standard_normal((kc, d)).astype(np.float32)
    centers /= np.linalg.norm(centers, axis=-1, keepdims=True)
    topic = (np.arange(n) * kc) // n
    core2 = centers[topic] + 0.05 * rng.standard_normal((n, d)).astype(
        np.float32
    )
    core2 /= np.linalg.norm(core2, axis=-1, keepdims=True)
    ts_range = 8192
    band = ts_range // kc
    attrs2 = rng.integers(0, 8, (n, m)).astype(np.int16)
    attrs2[:, 0] = (topic * band + rng.integers(0, band, n)).astype(np.int16)
    index2, _ = build_from_assignments(
        spec, jnp.asarray(centers), jnp.asarray(core2), jnp.asarray(attrs2),
        jnp.asarray(topic),
    )
    assert index2.summaries is not None
    index2 = dataclasses.replace(
        index2,
        centroids=jax.device_put(index2.centroids, shardings["centroids"]),
        vectors=jax.device_put(index2.vectors, shardings["vectors"]),
        attrs=jax.device_put(index2.attrs, shardings["attrs"]),
        ids=jax.device_put(index2.ids, shardings["ids"]),
        counts=jax.device_put(index2.counts, shardings["counts"]),
    )
    queries2 = jnp.asarray(core2[:q] + 0.01)
    w = band  # ~1-2 topics wide → most of the 4 probes prunable
    lo2 = np.full((q, 1, m), -32768, np.int16)
    hi2 = np.full((q, 1, m), 32767, np.int16)
    start = rng.integers(0, ts_range - w, q)
    lo2[:, 0, 0] = start.astype(np.int16)
    hi2[:, 0, 0] = (start + w - 1).astype(np.int16)
    fspec2 = FilterSpec(lo=jnp.asarray(lo2), hi=jnp.asarray(hi2))
    cm = np.asarray(can_match(index2.summaries, fspec2.lo, fspec2.hi))
    assert (~cm).sum() > 0, "window filter should exclude some clusters"
    ref2 = search_reference(index2, queries2, fspec2, k=20, n_probes=4)
    for backend in ("pallas_interpret", "xla_tiled"):
        outs = {}
        for prune in ("on", "off"):
            cfg_p = ShardedSearchConfig(
                k=20, n_probes=4, v_block=128, scan_q_block=8,
                backend=backend, prune=prune,
            )
            fn_p, _, _ = make_sharded_search(
                mesh, "dot", q_total=q, n_clusters=kc, cfg=cfg_p,
            )
            outs[prune] = fn_p(index2, queries2, fspec2)
        np.testing.assert_array_equal(
            np.asarray(outs["on"].ids), np.asarray(outs["off"].ids),
            err_msg=f"pruned != unpruned ids ({backend})",
        )
        np.testing.assert_array_equal(
            np.asarray(outs["on"].ids), np.asarray(ref2.ids),
            err_msg=f"pruned != reference ids ({backend})",
        )
    print("OK pruned dispatch == unpruned == reference "
          f"({int((~cm).sum())}/{cm.size} (q,cluster) pairs excluded)")

    # ---- straggler drop ----
    # Dropping shard 3 (clusters 6..7) must (a) never return an id stored in
    # those clusters, (b) keep every returned id filter-compliant, (c) not
    # grow the live-result count.  It MAY surface lower-ranked healthy
    # candidates that weren't in the full top-k — that is the designed
    # graceful degradation, not an error.
    shard_ok = jnp.ones((8,), jnp.bool_).at[3].set(False)
    res_drop = search_fn(index, queries, fspec, shard_ok)
    k_local = info["k_local"]
    dropped_cluster_ids = {
        int(i)
        for c in range(3 * k_local, 4 * k_local)
        for i in np.asarray(index.ids[c])
        if i >= 0
    }
    for row in np.asarray(res_drop.ids):
        for i in row:
            if i >= 0:
                assert int(i) not in dropped_cluster_ids
                assert attrs[i, 0] <= 5 and attrs[i, 1] >= 1
    n_live_drop = int(np.sum(np.asarray(res_drop.ids) >= 0))
    n_live_full = int(np.sum(np.asarray(res.ids) >= 0))
    assert n_live_drop <= n_live_full
    print("OK straggler drop is a sound partial merge")

    # ---- overflow accounting ----
    probe_ids = jnp.zeros((q, 4), jnp.int32)  # all probes hit shard 0
    sc, sq, sv, n_drop = dispatch_probes(
        probe_ids, n_shards=8, k_local=2, p_cap=8
    )
    assert int(n_drop) == q * 4 - 8, int(n_drop)
    assert int(jnp.sum(sv.astype(jnp.int32))) == 8
    print("OK overflow counted:", int(n_drop))

    # ---- p_cap sizing sanity ----
    assert probe_capacity(1024, 7, 512, 2.0) >= 2 * (1024 * 7 // 512)

    # ---- MoE combine: reduce-scatter == psum (§Perf optimization) ----
    import dataclasses as dc

    from repro.configs import deepseek_moe_16b
    from repro.models.transformer import forward, init_params

    cfg0 = deepseek_moe_16b.smoke_config()
    cfg0 = dc.replace(cfg0, dtype=jnp.float32, remat=False)
    params_t = init_params(jax.random.key(5), cfg0)
    toks = jnp.asarray(
        rng.integers(0, cfg0.vocab_size, (4, 32)).astype(np.int32)
    )
    outs = {}
    for combine in ("psum", "scatter"):
        cfgc = dc.replace(cfg0, moe_combine=combine)
        with jax.set_mesh(mesh):
            h, _ = jax.jit(
                lambda p, t: forward(p, cfgc, t, mesh=mesh,
                                     dp_axes=("data",))
            )(params_t, toks)
        outs[combine] = np.asarray(jax.device_get(h), np.float32)
    np.testing.assert_allclose(outs["psum"], outs["scatter"],
                               rtol=2e-4, atol=2e-4)
    print("OK MoE reduce-scatter combine == psum combine")

    print("ALL DISTRIBUTED SELFTESTS PASSED")


if __name__ == "__main__":
    main()
