"""EmbeddingBag for JAX (the brief's explicit gap): ``jnp.take`` +
``jax.ops.segment_sum``, with a vocab-sharded variant for pod-scale tables.

Two layouts:
  * fixed-width bags [B, L] with -1 padding (recsys histories) —
    :func:`embedding_bag`;
  * ragged multi-hot bags (flat ids + bag ids) — :func:`embedding_bag_ragged`
    via segment_sum, torch ``nn.EmbeddingBag`` semantics.

Sharding: tables are vocab-range-sharded over the ``model`` axis
(:func:`sharded_embedding_bag`, shard_map): each chip looks up only ids in
its range (out-of-range → 0 rows) and a psum over ``model`` assembles the
bag sums — the classic vocab-parallel embedding, with traffic [B, D] instead
of gathering table rows across chips.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


Array = jax.Array


def init_table(key: Array, vocab: int, dim: int, dtype=jnp.float32,
               stddev: float = 0.02) -> Array:
    return jax.nn.initializers.truncated_normal(stddev=stddev)(
        key, (vocab, dim), dtype
    )


def embedding_bag(
    table: Array,  # [V, D]
    ids: Array,  # [..., L] int32, -1 = padding
    *,
    mode: str = "sum",
    weights: Optional[Array] = None,  # [..., L]
) -> Array:
    """Fixed-width bag lookup+reduce. Returns [..., D]."""
    mask = (ids >= 0).astype(table.dtype)[..., None]
    rows = jnp.take(table, jnp.maximum(ids, 0), axis=0)  # [..., L, D]
    if weights is not None:
        rows = rows * weights[..., None].astype(table.dtype)
    rows = rows * mask
    s = jnp.sum(rows, axis=-2)
    if mode == "sum":
        return s
    if mode == "mean":
        n = jnp.maximum(jnp.sum(mask, axis=-2), 1.0)
        return s / n
    if mode == "max":
        neg = jnp.where(mask > 0, rows, -jnp.inf)
        return jnp.max(neg, axis=-2)
    raise ValueError(mode)


def embedding_bag_ragged(
    table: Array,  # [V, D]
    flat_ids: Array,  # [NNZ] int32
    bag_ids: Array,  # [NNZ] int32 — which bag each id belongs to
    n_bags: int,
    *,
    mode: str = "sum",
    weights: Optional[Array] = None,  # [NNZ]
) -> Array:
    """Ragged (true multi-hot) bags via segment_sum. Returns [n_bags, D]."""
    rows = jnp.take(table, jnp.maximum(flat_ids, 0), axis=0)
    valid = (flat_ids >= 0).astype(table.dtype)[:, None]
    if weights is not None:
        rows = rows * weights[:, None].astype(table.dtype)
    rows = rows * valid
    s = jax.ops.segment_sum(rows, bag_ids, num_segments=n_bags)
    if mode == "sum":
        return s
    if mode == "mean":
        n = jax.ops.segment_sum(valid, bag_ids, num_segments=n_bags)
        return s / jnp.maximum(n, 1.0)
    raise ValueError(mode)


def sharded_embedding_bag(
    table: Array,  # [V, D] — sharded P("model", None)
    ids: Array,  # [..., L] — replicated over "model"
    mesh: Mesh,
    *,
    mode: str = "sum",
    dp_axes: Tuple[str, ...] = ("data",),
) -> Array:
    """Vocab-parallel bag lookup: local-range take + psum over 'model'."""
    v = table.shape[0]
    n_model = mesh.shape["model"]
    v_local = v // n_model

    def local(tab, idl):
        me = jax.lax.axis_index("model")
        lo = me.astype(jnp.int32) * v_local
        rel = idl - lo
        inrange = jnp.logical_and(rel >= 0, rel < v_local)
        valid = jnp.logical_and(inrange, idl >= 0)
        rows = jnp.take(tab, jnp.clip(rel, 0, v_local - 1), axis=0)
        rows = rows * valid[..., None].astype(rows.dtype)
        out = jnp.sum(rows, axis=-2)
        out = jax.lax.psum(out, "model")
        if mode == "mean":
            n = jax.lax.psum(
                jnp.sum(valid.astype(rows.dtype), -1, keepdims=True), "model"
            )
            out = out / jnp.maximum(n, 1.0)
        return out

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P("model", None), P(dp_axes, *([None] * (ids.ndim - 1)))),
        out_specs=P(dp_axes, *([None] * (ids.ndim - 2)), None),
        check_vma=False,
    )(table, ids)
