"""The plain reference: filtered IVF search, written out in ``jax.numpy``.

It reads only what the benchmark made: the rows and attributes regenerated
from the seed, the partition's centroids and the list of each row.  Its
semantics are the paper's §4.4: score every centroid against the query,
probe the ``T`` best lists that hold any row, and return the ``k`` rows of
the largest dot product among the rows of those lists that pass the
query's conjunctive filter.  Scores are f32 at HIGHEST precision; with
bf16 rows, bf16-exact queries and bf16-exact centroids every product is
exact, so only the order of the f32 sums differs from the program's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NEG = -3.0e38  # a finite minus infinity, for masked scores
HIGHEST = jax.lax.Precision.HIGHEST
QBLOCK = 256  # queries per reference call, to bound its memory


@functools.partial(jax.jit, static_argnames=("t",))
def probe_lists(queries, centroids, counts, *, t: int):
    """[n, t+1] best lists of each query and their scores: the first ``t``
    are probed, the next one shows how close the choice was."""
    s = jnp.matmul(queries, centroids.T, precision=HIGHEST)
    s = jnp.where(counts[None, :] > 0, s, NEG)
    vals, ids = jax.lax.top_k(s, t + 1)
    return ids.astype(jnp.int32), vals


@functools.partial(jax.jit, static_argnames=("k",))
def _fold_chunk(run, x, a, lists, first, q, lo, hi, member, *, k: int):
    """Folds one chunk of rows into the running top-k of each query, for
    the probed lists (``member`` [n, K]) and for all lists (brute force)."""
    passes = jnp.all((a[None] >= lo[:, None]) & (a[None] <= hi[:, None]),
                     axis=-1)  # [n, c]
    probed = jnp.take(member, lists, axis=1)  # [n, c]
    s = jnp.matmul(q, x.astype(jnp.float32).T, precision=HIGHEST)
    ids = first + jnp.arange(x.shape[0], dtype=jnp.int32)
    out = []
    for (rv, ri), mask in zip(run, (probed & passes, passes)):
        v, j = jax.lax.top_k(jnp.where(mask, s, NEG), k)
        v = jnp.concatenate([rv, v], axis=1)
        i = jnp.concatenate([ri, ids[j]], axis=1)
        v, j = jax.lax.top_k(v, k)
        out.append((v, jnp.take_along_axis(i, j, axis=1)))
    return tuple(out)


def search(corpus, lists: np.ndarray, n_lists: int, queries, lo, hi,
           probes: np.ndarray, k: int):
    """Reference top-k of each query over its ``probes`` lists, and the
    brute-force top-k over every row.  Returns (scores, ids, bf_ids), with
    ids -1 and scores NEG where fewer than k rows pass."""
    n = len(queries)
    npad = -n % QBLOCK
    pad = lambda x: np.concatenate([x, np.repeat(x[-1:], npad, 0)])
    q, lo, hi, probes = map(pad, (queries, lo, hi, probes))
    member = np.zeros((len(q), n_lists), bool)
    np.put_along_axis(member, probes, True, axis=1)
    init = (jnp.full((QBLOCK, k), NEG, jnp.float32),
            jnp.full((QBLOCK, k), -1, jnp.int32))
    blocks = [tuple(jnp.asarray(v[s:s + QBLOCK]) for v in (q, lo, hi, member))
              for s in range(0, len(q), QBLOCK)]
    runs = [(init, init) for _ in blocks]
    for c in range(corpus.n_chunks):
        x, a = corpus.rows(c)
        lc = jnp.asarray(lists[c * corpus.chunk:(c + 1) * corpus.chunk])
        first = jnp.int32(c * corpus.chunk)
        runs = [_fold_chunk(run, x, a, lc, first, *blk, k=k)
                for run, blk in zip(runs, blocks)]
    outs = [[np.asarray(v) for pair in run for v in pair] for run in runs]
    ivf_s, ivf_i, bf_s, bf_i = (np.concatenate(p)[:n] for p in zip(*outs))
    ivf_i = np.where(ivf_s > NEG / 2, ivf_i, -1)
    bf_i = np.where(bf_s > NEG / 2, bf_i, -1)
    return ivf_s, ivf_i, bf_i


def compare(got_s, got_i, ref_s, ref_i, tol: float):
    """Served against reference top-k, query by query.

    Scores must agree position by position within ``tol·(1 + |ref|)``.
    Ids must be equal, except where two candidates' reference scores lie
    within that tolerance of each other: the order of such a tie is
    decided by the order of f32 sums, which kernel and reference do not
    share, and the swap is counted.  Returns the widest relative score gap,
    the queries whose answer differs, and the tie swaps.
    """
    live = (ref_s > NEG / 2) | (got_s > NEG / 2)
    gap = np.abs(got_s.astype(np.float64) - ref_s) / (1.0 + np.abs(ref_s))
    gap = np.where(live, gap, 0.0)
    lim = tol * (1.0 + np.abs(ref_s))
    bad, swaps = [], 0
    for r in range(len(ref_s)):
        if (gap[r] > tol).any():
            bad.append(r)
            continue
        g = got_i[r]
        if len(set(g[g >= 0].tolist())) != int((g >= 0).sum()):
            bad.append(r)  # a duplicate id
            continue
        where = {int(i): p for p, i in enumerate(ref_i[r])}
        for p in np.nonzero(g != ref_i[r])[0]:
            j = where.get(int(g[p]))
            partner = ref_s[r][j] if j is not None else ref_s[r][-1]
            if g[p] < 0 or abs(partner - ref_s[r][p]) > lim[r][p]:
                bad.append(r)
                break
            swaps += 1
    return dict(score_gap=float(gap.max(initial=0.0)), bad=bad,
                tie_swaps=swaps)


def answer_faults(ids, lo, hi, attrs, n_rows: int) -> np.ndarray:
    """[n] bool: an answer names a row out of range, a row twice, a row
    that fails the request's filter, or a live row after a missing one."""
    live = ids >= 0
    out = ((ids >= n_rows) | (ids < -1)).any(axis=1)
    out |= (~live[:, :-1] & live[:, 1:]).any(axis=1)
    a = attrs[np.clip(ids, 0, n_rows - 1)]  # [n, k, M]
    ok = ((a >= lo[:, None]) & (a <= hi[:, None])).all(axis=-1)
    out |= (live & ~ok).any(axis=1)
    srt = np.sort(np.where(live, ids, -1 - np.arange(ids.shape[1])), axis=1)
    out |= (srt[:, 1:] == srt[:, :-1]).any(axis=1)
    return out


def recall(got_i, bf_i) -> float:
    """Share of the brute-force top-k ids that the answers hold."""
    hit = (bf_i[:, :, None] == got_i[:, None, :]).any(-1) & (bf_i >= 0)
    return float(hit.sum() / max(int((bf_i >= 0).sum()), 1))
