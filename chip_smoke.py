"""Smoke run of the filtered IVF search path on a TPU chip.

Drives the path a deployment serves — ``build_ivf`` on the chip, then
``make_fused_search_fn`` → ``SearchServer`` → ``SearchEngine`` → the
compiled Pallas kernel ``filtered_scan_tiled`` — at the geometry of the
paper's case study (arXiv 2501.13442 §5: 768-d CLIP rows, 10 int16
attributes, mean list length 31,250, k=100, T=7), and checks every answer
against ``search_reference`` run on the same chip and recall@100 against
brute force.

    python chip_smoke.py               # one chip: RAM bf16, SQ8, disk tier
    python chip_smoke.py --four-chips  # four chips: the cluster-sharded index

Data comes from ``--seed``: 2M unit rows around 1024 random topic centres,
and 10 attributes drawn independently of the content.  Queries are rounded
to bf16, the dtype the lists are stored and scanned in.  The last line of
stdout is ``{"ok": true, "device": {...}}``; a failed phase exits non-zero
before it, and so does a run on anything but a TPU.  ``--cpu-rehearsal``
runs the same control flow on the CPU at 20,000 rows, with the kernels in
interpret mode, and never reports ok.
"""

from __future__ import annotations

import argparse
import functools
import json
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

from repro.launch import use_compile_cache  # noqa: E402

D, M, K, TOPK, T, BATCH = 768, 10, 64, 100, 7, 64
N_ROWS = 2_000_000  # 64 lists × the paper's mean list length of 31,250
REHEARSAL_ROWS = 20_000
CHUNK = 62_500  # rows generated (and brute-forced) per device call
N_TOPICS, NOISE = 1024, 0.03
# Attribute columns: a0 a time bucket, a1 a 10-way category, a2 a flag,
# a3..a9 carried but not filtered on (the paper's rows carry 10).
ATTR_CARD = (10_000, 10, 2) + (1_000,) * 7
REQUESTS_PER_CLASS = 64
CLASSES = ("unfiltered", "sel~50%", "sel~5%", "sel~0.5%")
TOL = 1e-5  # |served − reference| ≤ TOL·(1 + |reference|), as in the tests
SMOKE_DIR = ROOT / ".smoke"


def log(*parts):
    print(*parts, flush=True)


class CompileClock:
    """Sums the backend compile seconds JAX reports."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds = 0.0

        def on_event(name, secs, **_):
            if name == self.EVENT:
                self.seconds += secs

        jax.monitoring.register_event_duration_secs_listener(on_event)


def device_bytes():
    import jax

    out = []
    for d in jax.devices():
        st = d.memory_stats() or {}
        out.append((st.get("bytes_in_use"), st.get("peak_bytes_in_use")))
    return out


# ---------------------------------------------------------------------------
# Data, generated on the device from the seed
# ---------------------------------------------------------------------------


def make_generator(seed: int):
    import jax
    import jax.numpy as jnp

    key = jax.random.key(seed)
    kc, kr, kq = jax.random.split(key, 3)
    centers = jax.random.normal(kc, (N_TOPICS, D), jnp.float32)
    centers = centers / jnp.linalg.norm(centers, axis=-1, keepdims=True)
    card = jnp.asarray(ATTR_CARD, jnp.int32)

    @functools.partial(jax.jit, static_argnames=("rows",))
    def rows_of_chunk(i, *, rows):
        kt, kn, ka = jax.random.split(jax.random.fold_in(kr, i), 3)
        topic = jax.random.randint(kt, (rows,), 0, N_TOPICS)
        x = centers[topic] + NOISE * jax.random.normal(kn, (rows, D))
        x = x / jnp.linalg.norm(x, axis=-1, keepdims=True)
        a = jax.random.randint(ka, (rows, M), 0, card)
        return x.astype(jnp.bfloat16), a.astype(jnp.int16)

    def queries(n):
        kt, kn = jax.random.split(kq)
        topic = jax.random.randint(kt, (n,), 0, N_TOPICS)
        x = centers[topic] + NOISE * jax.random.normal(kn, (n, D))
        x = x / jnp.linalg.norm(x, axis=-1, keepdims=True)
        return np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32))

    return rows_of_chunk, queries


def generate(rows_of_chunk, n_rows: int, chunk: int):
    """All rows in one device buffer, filled chunk by chunk in place."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def put(core, attrs, c, a, start):
        return (jax.lax.dynamic_update_slice(core, c, (start, 0)),
                jax.lax.dynamic_update_slice(attrs, a, (start, 0)))

    core = jnp.zeros((n_rows, D), jnp.bfloat16)
    attrs = jnp.zeros((n_rows, M), jnp.int16)
    for i in range(n_rows // chunk):
        c, a = rows_of_chunk(i, rows=chunk)
        core, attrs = put(core, attrs, c, a, i * chunk)
    return core, attrs


def make_requests(n_per_class: int, rng: np.random.Generator):
    """(class, lo [1, M], hi [1, M]) per request: conjunctive range and
    equality filters at about 50%, 5% and 0.5% selectivity."""
    from repro.core import FilterBuilder

    out = []
    for cls in CLASSES:
        for _ in range(n_per_class):
            f = FilterBuilder(M)
            if cls == "sel~50%":  # a1 ≤ 7 (80%) ∧ a0 window (62.5%)
                s = int(rng.integers(0, 10_000 - 6_250))
                f.le(1, 7).between(0, s, s + 6_249)
            elif cls == "sel~5%":  # a1 == c (10%) ∧ a0 window (50%)
                s = int(rng.integers(0, 10_000 - 5_000))
                f.eq(1, int(rng.integers(0, 10))).between(0, s, s + 4_999)
            elif cls == "sel~0.5%":  # a1 == c ∧ a2 == 0 ∧ a0 window (10%)
                s = int(rng.integers(0, 10_000 - 1_000))
                f.eq(1, int(rng.integers(0, 10))).eq(2, 0)
                f.between(0, s, s + 999)
            lo, hi = f.intervals()
            out.append((cls, lo, hi))
    return out


# ---------------------------------------------------------------------------
# Oracles and checks
# ---------------------------------------------------------------------------


def brute_force_all(rows_of_chunk, n_rows, chunk, queries, lo, hi):
    """Exact filtered top-k over every row, regenerated chunk by chunk."""
    import jax.numpy as jnp
    from repro.core import brute_force
    from repro.core.filters import FilterSpec
    from repro.core.topk import merge_topk

    fspec = FilterSpec(lo=jnp.asarray(lo), hi=jnp.asarray(hi))
    q = jnp.asarray(queries)
    best = None
    passed = np.zeros(len(queries), np.int64)
    for i in range(n_rows // chunk):
        c, a = rows_of_chunk(i, rows=chunk)
        ids = jnp.arange(i * chunk, (i + 1) * chunk, dtype=jnp.int32)
        r = brute_force(c, a, q, fspec, k=TOPK, metric="dot", ids=ids)
        passed += np.asarray(r.n_passed)
        part = (r.scores, r.ids)
        best = part if best is None else merge_topk(best, part, TOPK)
    return np.asarray(best[0]), np.asarray(best[1]), passed / n_rows


def reference_all(index, queries, lo, hi, step: int = 2):
    """search_reference over all requests, ``step`` queries per call (it
    materializes the [Q, T, Vpad, D] gather of the probed lists)."""
    import jax.numpy as jnp
    from repro.core.filters import FilterSpec
    from repro.core.search import search_reference

    scores, ids = [], []
    for s in range(0, len(queries), step):
        fs = FilterSpec(lo=jnp.asarray(lo[s:s + step]),
                        hi=jnp.asarray(hi[s:s + step]))
        r = search_reference(index, jnp.asarray(queries[s:s + step]), fs,
                             k=TOPK, n_probes=T)
        scores.append(np.asarray(r.scores))
        ids.append(np.asarray(r.ids))
    return np.concatenate(scores), np.concatenate(ids)


def compare(got_s, got_i, ref_s, ref_i):
    """Served vs reference top-k, per query.

    Scores must agree position by position within TOL.  Ids must be equal,
    except where two candidates' reference scores lie within TOL of each
    other (their order is then decided by f32 summation order, which the
    kernel and XLA's einsum do not share): such a tie swap is counted.
    """
    tol = TOL * (1.0 + np.abs(ref_s))
    score_diff = np.abs(got_s - ref_s)
    bad = []
    swaps = 0
    for r in range(len(ref_s)):
        if not (score_diff[r] <= tol[r]).all():
            bad.append(r)
            continue
        if len(set(got_i[r][got_i[r] >= 0])) != int((got_i[r] >= 0).sum()):
            bad.append(r)  # a duplicate id
            continue
        where = {int(i): p for p, i in enumerate(ref_i[r])}
        for p in np.nonzero(got_i[r] != ref_i[r])[0]:
            q = where.get(int(got_i[r][p]))
            partner = (ref_s[r][q] if q is not None else ref_s[r][-1])
            if abs(partner - ref_s[r][p]) > tol[r][p]:
                bad.append(r)
                break
            swaps += 1
    live = ref_s > -1e38
    max_diff = float(score_diff[live].max()) if live.any() else 0.0
    return dict(queries=len(ref_s), mismatched=len(bad), tie_swaps=swaps,
                ids_identical=int((got_i == ref_i).all(axis=1).sum()),
                max_score_diff=max_diff)


def recall(got_i, oracle_i):
    from repro.core.search import SearchResult, recall_at_k

    z = np.zeros(len(got_i), np.int32)
    return recall_at_k(SearchResult(None, got_i, z, z),
                       SearchResult(None, oracle_i, z, z))


# ---------------------------------------------------------------------------
# One-chip phases
# ---------------------------------------------------------------------------


def serve(target, requests, queries, backend, clock):
    """Serves every request through SearchServer.

    ``backend=None`` lets the engine choose, as a deployment does; on a TPU
    it must choose the compiled kernel.  Returns (scores, ids, info).
    """
    from repro.core.serving import SearchServer, make_fused_search_fn

    fn = make_fused_search_fn(target, k=TOPK, n_probes=T, q_block=BATCH,
                              backend=backend)
    expect = backend or "pallas"
    if fn.engine.backend != expect:
        raise RuntimeError(f"engine backend {fn.engine.backend!r}, "
                           f"expected {expect!r}")
    server = SearchServer(fn, batch_size=BATCH, dim=D, n_attrs=M, n_terms=1,
                          n_shards=1, max_wait_s=0.05)
    c0, t0 = clock.seconds, time.perf_counter()
    server.start()
    try:
        futs = [server.submit(queries[i], (lo, hi))
                for i, (_, lo, hi) in enumerate(requests)]
        resps = [f.get(timeout=900) for f in futs]
    finally:
        server.stop()
        fn.close()
    wall = time.perf_counter() - t0
    return (np.stack([r.scores for r in resps]),
            np.stack([r.ids for r in resps]),
            dict(backend=fn.engine.backend, requests=len(resps),
                 batches=server.stats["batches"],
                 failed_batches=server.stats["failed_batches"],
                 compile_s=round(clock.seconds - c0, 2),
                 wall_s_incl_compile=round(wall, 2)))


def report_phase(name, info, got, ref, oracle_i, requests):
    cmp = compare(got[0], got[1], *ref)
    classes = np.asarray([c for c, _, _ in requests])
    info.update(check=cmp, recall_at_100=round(recall(got[1], oracle_i), 4),
                recall_by_class={
                    c: round(recall(got[1][classes == c],
                                    oracle_i[classes == c]), 4)
                    for c in CLASSES})
    log(f"phase {name}: " + json.dumps(info))
    if cmp["mismatched"] or info["requests"] != len(requests):
        raise RuntimeError(f"phase {name} does not match search_reference")


def one_chip(args, clock, backend):
    import jax
    from repro.core import HybridSpec, build_ivf
    from repro.core import storage
    from repro.core.disk import DiskIVFIndex
    from repro.core.ivf import quantize_index

    rows_of_chunk, make_queries = make_generator(args.seed)
    chunk = min(CHUNK, args.rows)
    n_rows = args.rows - args.rows % chunk
    requests = make_requests(REQUESTS_PER_CLASS, np.random.default_rng(
        args.seed))
    order = np.random.default_rng(args.seed + 1).permutation(len(requests))
    requests = [requests[i] for i in order]  # classes interleaved in batches
    queries = make_queries(len(requests))
    lo = np.stack([r[1] for r in requests])
    hi = np.stack([r[2] for r in requests])

    t0, c0 = time.perf_counter(), clock.seconds
    core, attrs = generate(rows_of_chunk, n_rows, chunk)
    index, stats = build_ivf(jax.random.key(args.seed), HybridSpec(D, M),
                             core, attrs, n_clusters=K)
    jax.block_until_ready(index.vectors)
    del core, attrs
    build_s = time.perf_counter() - t0
    if stats.n_dropped:
        raise RuntimeError(f"build dropped {stats.n_dropped} rows")
    log("sizes: " + json.dumps(dict(
        N=n_rows, d=D, m=M, K=K, k=TOPK, T=T, batch=BATCH,
        Vpad=stats.vpad, max_list=stats.max_list_len,
        mean_list=stats.mean_list_len, store="bfloat16",
        index_bytes=index.nbytes(), device_bytes=device_bytes()[0],
        build_s=round(build_s, 2),
        build_compile_s=round(clock.seconds - c0, 2))))

    b_s, b_i, sel = brute_force_all(rows_of_chunk, n_rows, chunk, queries,
                                    lo, hi)
    classes = np.asarray([c for c, _, _ in requests])
    log("selectivity: " + json.dumps(
        {c: round(float(sel[classes == c].mean()), 5) for c in CLASSES}))

    got_s, got_i, info = serve(index, requests, queries, backend, clock)
    ref_bf16 = reference_all(index, queries, lo, hi)
    info["device_bytes"] = device_bytes()[0]
    report_phase("ram-bf16", info, (got_s, got_i), ref_bf16, b_i, requests)

    ckpt = SMOKE_DIR / "ckpt"
    shutil.rmtree(ckpt, ignore_errors=True)
    t0 = time.perf_counter()
    storage.save_index(index, str(ckpt))
    save_s = time.perf_counter() - t0

    qindex = quantize_index(index)
    del index
    got_s, got_i, info = serve(qindex, requests, queries, backend, clock)
    ref_sq8 = reference_all(qindex, queries, lo, hi)
    info["device_bytes"] = device_bytes()[0]
    report_phase("ram-sq8", info, (got_s, got_i), ref_sq8, b_i, requests)
    del qindex

    try:
        # a quarter of the lists fit the host cache; the rest page in
        budget = int(0.25 * (ckpt / "shard_0_of_1.bin").stat().st_size)
        disk = DiskIVFIndex.open(str(ckpt), resident_budget_bytes=budget)
        try:
            got_s, got_i, info = serve(disk, requests, queries, backend,
                                       clock)
            info.update(resident_budget_bytes=budget,
                        resident_bytes=disk.resident_bytes(),
                        cache_records=disk.cache.capacity_records,
                        checkpoint_save_s=round(save_s, 2),
                        device_bytes=device_bytes()[0])
        finally:
            disk.close()
        report_phase("disk", info, (got_s, got_i), ref_bf16, b_i, requests)
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)


# ---------------------------------------------------------------------------
# Four-chip phase
# ---------------------------------------------------------------------------


def four_chips(args, clock, backend):
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    from repro.core import HybridSpec, build_ivf
    from repro.core.distributed import ShardedSearchConfig, make_sharded_search
    from repro.core.filters import FilterSpec

    n_dev = len(jax.devices())
    if n_dev != 4:
        raise RuntimeError(f"--four-chips needs 4 devices, found {n_dev}")
    mesh = jax.make_mesh((4,), ("shard",), axis_types=(AxisType.Auto,))
    rows_of_chunk, make_queries = make_generator(args.seed)
    chunk = min(CHUNK, args.rows)
    n_rows = args.rows - args.rows % chunk
    requests = make_requests(BATCH // len(CLASSES),
                             np.random.default_rng(args.seed))
    queries = make_queries(len(requests))
    lo = np.stack([r[1] for r in requests])
    hi = np.stack([r[2] for r in requests])

    core, attrs = generate(rows_of_chunk, n_rows, chunk)
    index, stats = build_ivf(jax.random.key(args.seed), HybridSpec(D, M),
                             core, attrs, n_clusters=K)
    del core, attrs
    cfg = ShardedSearchConfig(k=TOPK, n_probes=T, scan_q_block=BATCH,
                              backend=backend)
    search_fn, shardings, info = make_sharded_search(
        mesh, "dot", q_total=len(requests), n_clusters=K, cfg=cfg)
    repl = NamedSharding(mesh, P())
    placed = {f: jax.device_put(getattr(index, f), shardings[f])
              for f in ("centroids", "vectors", "attrs", "ids", "counts")}
    sharded = dataclasses.replace(
        index, **placed,
        summaries=jax.device_put(index.summaries, repl))
    replicated = jax.device_put(index, repl)
    del index
    shard_rows = {s.device.id: s.data.shape[0]
                  for s in sharded.vectors.addressable_shards}
    log("sizes: " + json.dumps(dict(
        N=n_rows, d=D, m=M, K=K, k=TOPK, T=T, batch=len(requests),
        Vpad=stats.vpad, chips=n_dev, lists_per_chip=shard_rows,
        p_cap=info["p_cap"], device_bytes=device_bytes())))
    if sorted(shard_rows.values()) != [K // 4] * 4:
        raise RuntimeError(f"lists not spread over the chips: {shard_rows}")

    fspec = FilterSpec(lo=jnp.asarray(lo), hi=jnp.asarray(hi))
    c0 = clock.seconds
    res = jax.jit(search_fn)(sharded, jnp.asarray(queries), fspec)
    got_s, got_i = np.asarray(res.scores), np.asarray(res.ids)
    overflow = int(np.asarray(res.n_scanned)[0])
    ref = reference_all(replicated, queries, lo, hi)
    b_s, b_i, _ = brute_force_all(rows_of_chunk, n_rows, chunk, queries,
                                  lo, hi)
    info = dict(backend=backend, requests=len(requests),
                probes_overflowed=overflow,
                compile_s=round(clock.seconds - c0, 2),
                device_bytes=device_bytes())
    report_phase("sharded-4chip", info, (got_s, got_i), ref, b_i, requests)
    if overflow:
        raise RuntimeError(f"{overflow} probes overflowed the slot table")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the cluster-sharded index on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run on the CPU at 20k rows with the kernels "
                         "interpreted; never reports ok")
    args = ap.parse_args()
    args.rows = REHEARSAL_ROWS if args.cpu_rehearsal else N_ROWS

    use_compile_cache()
    import jax

    dev = jax.devices()[0]
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=len(jax.devices()))
    if args.cpu_rehearsal:
        backend = ("pallas_tiled_interpret" if args.four_chips
                   else "pallas_interpret")
    elif dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found platform {dev.platform!r}",
              file=sys.stderr)
        return 2
    else:  # the sharded path names its kernel; the engine picks its own
        backend = "pallas_tiled" if args.four_chips else None
    log("device: " + json.dumps(device))
    clock = CompileClock()
    SMOKE_DIR.mkdir(exist_ok=True)
    (four_chips if args.four_chips else one_chip)(args, clock, backend)
    log(f"compile seconds (backend, all phases): {clock.seconds:.2f}")
    if args.cpu_rehearsal:
        log(json.dumps(dict(ok=False, rehearsal="cpu", device=device)))
        return 3
    log(json.dumps(dict(ok=True, device=device)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
