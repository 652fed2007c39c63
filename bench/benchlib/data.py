"""Rows, attributes, the list partition and queries, made on the device.

The corpus follows the paper's case study (arXiv 2501.13442 §5): unit
768-d rows in bf16 around random topic centres, each topic holding the same
number of rows, and int16 attributes drawn independently of the content.
What varies with ``--seed`` is the rows, the attributes and the traffic.  What a configuration fixes through its
``corpus_seed`` is the structure every seed shares: the topic centres and
the list partition (centroids trained once on a sample, as an IVF coarse
quantizer is), so that every seed gives lists of the same sizes.

The partition is the benchmark's own: spherical k-means (k-means++ seeding,
then Lloyd steps) on the sample, and each row assigned to the centroid with
the largest dot product.  The program under test receives the centroids and
the assignment and builds its lists from them; the reference needs nothing
the program made.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def run_key(seed: int):
    """A JAX key from a seed of any size (the driver's exceed 32 bits)."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@functools.partial(jax.jit, static_argnames=("n", "dim"))
def _topic_centers(key, *, n: int, dim: int):
    c = jax.random.normal(key, (n, dim), jnp.float32)
    return c / jnp.linalg.norm(c, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("rows", "noise"))
def _rows(key, centers, card, i, *, rows: int, noise: float):
    kn, ka = jax.random.split(jax.random.fold_in(key, i))
    # every topic holds the same number of rows under every seed, so the
    # lists have the same sizes; the seed moves the rows within their topic
    topic = (i * rows + jnp.arange(rows)) % centers.shape[0]
    x = centers[topic] + noise * jax.random.normal(
        kn, (rows, centers.shape[1]))
    x = x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    a = jax.random.randint(ka, (rows, card.shape[0]), 0, card)
    return x.astype(jnp.bfloat16), a.astype(jnp.int16)


@functools.partial(jax.jit, static_argnames=("noise",))
def _queries(key, centers, topics, *, noise: float):
    x = centers[topics] + noise * jax.random.normal(
        key, (topics.shape[0], centers.shape[1]))
    return (x / jnp.linalg.norm(x, axis=-1, keepdims=True)).astype(
        jnp.bfloat16)


def bf16_exact(x) -> np.ndarray:
    """f32 values that bf16 holds exactly, widened on the host.  Inside a
    jitted function XLA may drop an f32 -> bf16 -> f32 round trip (it
    allows excess precision), so the rounding is kept apart from it."""
    return np.asarray(x).astype(np.float32)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _put(core, attrs, c, a, start):
    return (jax.lax.dynamic_update_slice(core, c, (start, 0)),
            jax.lax.dynamic_update_slice(attrs, a, (start, 0)))


@functools.partial(jax.jit, static_argnames=("n_lists", "iters"))
def _spherical_kmeans(key, x, *, n_lists: int, iters: int):
    """k-means++ seeding, then Lloyd steps with unit centroids."""
    x = x.astype(jnp.float32)
    s = x.shape[0]
    k0, kl = jax.random.split(key)
    first = jax.random.randint(k0, (), 0, s)
    cent = jnp.zeros((n_lists, x.shape[1]), jnp.float32).at[0].set(x[first])
    dot = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    dist = jnp.maximum(2.0 - 2.0 * dot(x, x[first]), 0.0)

    def seed_one(j, carry):
        cent, dist = carry
        pick = jax.random.categorical(
            jax.random.fold_in(kl, j), jnp.log(jnp.maximum(dist, 1e-30)))
        c = x[pick]
        return (cent.at[j].set(c),
                jnp.minimum(dist, jnp.maximum(2.0 - 2.0 * dot(x, c), 0.0)))

    cent, _ = jax.lax.fori_loop(1, n_lists, seed_one, (cent, dist))

    def unit(v):
        return v / jnp.maximum(jnp.linalg.norm(v, axis=-1, keepdims=True),
                               1e-30)

    def lloyd(i, cent):
        a = jnp.argmax(dot(x, cent.T), axis=-1)
        sums = jax.ops.segment_sum(x, a, num_segments=n_lists)
        count = jax.ops.segment_sum(jnp.ones((s,), jnp.int32), a,
                                    num_segments=n_lists)
        cent = jnp.where((count > 0)[:, None], unit(sums), cent)
        # an empty list takes half of the largest ones, as FAISS does: the
        # donor and the empty centroid move apart by a small random step
        empty = count == 0
        rank = jnp.cumsum(empty) - 1
        donor = jnp.argsort(-count)[jnp.clip(rank, 0, n_lists - 1)]
        step = 1e-2 * unit(jax.random.normal(
            jax.random.fold_in(kl, n_lists + i), cent.shape))
        to = jnp.where(empty, jnp.arange(n_lists), n_lists)
        moved = cent.at[jnp.where(empty, donor, n_lists)].set(
            unit(cent[donor] - step), mode="drop")
        return moved.at[to].set(unit(cent[donor] + step), mode="drop")

    return jax.lax.fori_loop(0, iters, lloyd, cent).astype(jnp.bfloat16)


@jax.jit
def _assign(x, centroids):
    s = jnp.matmul(x.astype(jnp.float32), centroids.T,
                   precision=jax.lax.Precision.HIGHEST)
    return jnp.argmax(s, axis=-1).astype(jnp.int32)


class Corpus:
    """One run's data: the configuration's fixed structure plus the rows of
    ``seed``.  Rows are regenerated chunk by chunk on demand, so the
    reference can read them after the program's state is freed."""

    def __init__(self, cfg: dict, seed: int):
        self.cfg = cfg
        self.dim = cfg["dim"]
        self.n_attrs = cfg["n_attrs"]
        self.chunk = cfg["chunk_rows"]
        self.n_rows = cfg["rows"] - cfg["rows"] % self.chunk
        self.noise = float(cfg["topic_noise"])
        fixed = jax.random.key(cfg["corpus_seed"])
        self.centers = _topic_centers(
            jax.random.fold_in(fixed, 0), n=cfg["n_topics"], dim=self.dim)
        self.card = jnp.asarray(cfg["attr_cardinality"], jnp.int32)
        if self.card.shape[0] != self.n_attrs:
            raise ValueError("attr_cardinality needs one entry per attribute")
        self._fixed = fixed
        self._rows_key = jax.random.fold_in(run_key(seed), 1)
        self._query_key = jax.random.fold_in(run_key(seed), 2)

    @property
    def n_chunks(self) -> int:
        return self.n_rows // self.chunk

    def rows(self, i: int):
        """Chunk ``i``: rows ``[i·chunk, (i+1)·chunk)`` as (bf16, int16)."""
        return _rows(self._rows_key, self.centers, self.card, i,
                     rows=self.chunk, noise=self.noise)

    def all_rows(self, centroids):
        """Every row in one device buffer, filled chunk by chunk, with the
        list of each row: (core [N, D] bf16, attrs [N, M] int16, list [N])."""
        core = jnp.zeros((self.n_rows, self.dim), jnp.bfloat16)
        attrs = jnp.zeros((self.n_rows, self.n_attrs), jnp.int16)
        lists = []
        for i in range(self.n_chunks):
            c, a = self.rows(i)
            lists.append(_assign(c, centroids))
            core, attrs = _put(core, attrs, c, a, i * self.chunk)
        return core, attrs, jnp.concatenate(lists)

    def centroids(self):
        """The configuration's list centroids [K, D] f32, bf16-exact: probe
        scores of bf16 queries are then exact products summed in f32, in
        the program and in the reference alike."""
        km = self.cfg["kmeans"]
        sample, _ = _rows(jax.random.fold_in(self._fixed, 1), self.centers,
                          self.card, 0, rows=km["sample_rows"],
                          noise=self.noise)
        return jnp.asarray(bf16_exact(_spherical_kmeans(
            jax.random.fold_in(self._fixed, 2), sample,
            n_lists=self.cfg["n_lists"], iters=km["iters"])))

    def queries(self, topics: np.ndarray, stream: int) -> np.ndarray:
        """Unit queries around the given topics, bf16-exact, on the host;
        each ``stream`` draws its own noise."""
        n = len(topics)
        pad = -n % 1024  # few distinct shapes to compile
        t = np.concatenate([topics, np.zeros(pad, topics.dtype)])
        key = jax.random.fold_in(self._query_key, stream)
        return bf16_exact(_queries(key, self.centers,
                                   jnp.asarray(t, jnp.int32),
                                   noise=self.noise))[:n]
