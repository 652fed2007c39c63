"""The scan work that a batch of searches needs, whatever implements it.

For one batch, with each query's probed lists taken from the benchmark's
own centroid scoring:

- bytes: the attribute bytes of every live row in the union of the probed
  lists, plus the vector bytes of every live row that passes the filter of
  at least one query that probes its list;
- operations: ``2·d`` for every (query, live row of a probed list that
  passes the query's filter) pair.

Padding, slot tables and grids are not counted: a program that skips work
the search does not need raises its share of the roofline.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=("dim", "row_bytes"))
def _batch_work(probes, valid, lo, hi, list_attrs, list_count, *, dim: int,
                row_bytes: int):
    """probes [B, T], valid [B], lo/hi [B, M], list_attrs [K, V, M],
    list_count [K] -> (bytes, operations) as f32 scalars."""
    k, v, m = list_attrs.shape

    def one(t):
        p = probes[:, t]  # [B]
        a = list_attrs[p]  # [B, V, M]
        ok = jnp.all((a >= lo[:, None]) & (a <= hi[:, None]), axis=-1)
        live = jnp.arange(v)[None, :] < list_count[p][:, None]
        return ok & live & valid[:, None]  # [B, V]

    passes = jax.lax.map(one, jnp.arange(probes.shape[1]))  # [T, B, V]
    flops = 2.0 * dim * jnp.sum(passes, dtype=jnp.float32)
    flat = passes.reshape(-1, v)
    target = jnp.where(valid[None, :], probes.T, k).reshape(-1)
    needed = jnp.zeros((k, v), jnp.int8).at[target].max(
        flat.astype(jnp.int8), mode="drop")
    probed = jnp.zeros((k,), bool).at[target].set(True, mode="drop")
    attr_rows = jnp.sum(jnp.where(probed, list_count, 0), dtype=jnp.float32)
    vec_rows = jnp.sum(needed, dtype=jnp.float32)
    return attr_rows * m * 2 + vec_rows * row_bytes, flops


class ScanWork:
    """The benchmark's lists laid out for counting: attributes of each
    list's rows, padded to the longest list."""

    def __init__(self, lists: np.ndarray, attrs: np.ndarray, n_lists: int,
                 dim: int, row_bytes: int):
        order = np.argsort(lists, kind="stable")
        count = np.bincount(lists, minlength=n_lists)
        pos = np.arange(len(lists)) - np.repeat(np.cumsum(count) - count,
                                                count)
        la = np.zeros((n_lists, max(int(count.max()), 1), attrs.shape[1]),
                      attrs.dtype)
        la[lists[order], pos] = attrs[order]
        self.list_attrs = jnp.asarray(la)
        self.list_count = jnp.asarray(count.astype(np.int32))
        self.dim, self.row_bytes = dim, row_bytes

    def batch(self, probes: np.ndarray, lo: np.ndarray, hi: np.ndarray,
              size: int):
        """(bytes, operations) for one batch of ``len(probes)`` queries,
        padded to ``size`` rows so that every batch shares one program."""
        b = len(probes)
        pad = lambda x: np.concatenate(
            [x, np.zeros((size - b,) + x.shape[1:], x.dtype)])
        n_bytes, flops = _batch_work(
            jnp.asarray(pad(probes)), jnp.asarray(np.arange(size) < b),
            jnp.asarray(pad(lo)), jnp.asarray(pad(hi)), self.list_attrs,
            self.list_count, dim=self.dim, row_bytes=self.row_bytes)
        return float(n_bytes), float(flops)
