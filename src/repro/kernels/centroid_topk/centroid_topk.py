"""Streaming centroid top-T (paper §4.4 step 2) as a Pallas kernel.

Computes ``top-T_k( score(q, c_k) )`` for a batch of queries against the full
centroid table without ever writing the [Q, K] score matrix to HBM: each grid
step scores one (query-block × centroid-block) tile on the MXU and folds it
into a running top-T held in VMEM scratch.  At K=32 768, Q=1024 that removes a
128 MiB HBM round-trip per batch.

The in-kernel selection is iterative max-extraction (T static iterations of
max/argmax over the tile ∪ running set) — branch-free, Mosaic-friendly, and
exact; no reliance on sort lowering inside the kernel.

Grid: (Q//q_block, K//k_block), centroid axis innermost so the running state
for a query block sees every centroid tile before the output write.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -3.0e38


def _kernel(q_ref, c_ref, ov_ref, oi_ref, rv_ref, ri_ref, *, t, k_block,
            metric):
    ki = pl.program_id(1)
    nkb = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        rv_ref[...] = jnp.full_like(rv_ref, NEG_INF)
        ri_ref[...] = jnp.full_like(ri_ref, -1)

    q = q_ref[...].astype(jnp.float32)  # [QB, D]
    c = c_ref[...].astype(jnp.float32)  # [KB, D]
    scores = jax.lax.dot_general(
        q, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [QB, KB]
    if metric == "l2":
        scores = 2.0 * scores - jnp.sum(c * c, axis=-1)[None, :]

    qb = scores.shape[0]
    base = ki * k_block
    tile_ids = jax.lax.broadcasted_iota(jnp.int32, (qb, k_block), 1) + base

    cand_v = jnp.concatenate([rv_ref[...], scores], axis=1)  # [QB, T+KB]
    cand_i = jnp.concatenate([ri_ref[...], tile_ids], axis=1)

    new_v = []
    new_i = []
    for _ in range(t):  # static T-step extraction
        m = jnp.max(cand_v, axis=1)  # [QB]
        am = jnp.argmax(cand_v, axis=1)  # [QB]
        picked = jnp.take_along_axis(cand_i, am[:, None], axis=1)[:, 0]
        new_v.append(m)
        new_i.append(jnp.where(m > NEG_INF / 2, picked, -1))
        hit = (
            jax.lax.broadcasted_iota(jnp.int32, cand_v.shape, 1)
            == am[:, None]
        )
        cand_v = jnp.where(hit, NEG_INF, cand_v)
    rv_ref[...] = jnp.stack(new_v, axis=1)
    ri_ref[...] = jnp.stack(new_i, axis=1)

    @pl.when(ki == nkb - 1)
    def _emit():
        ov_ref[...] = rv_ref[...]
        oi_ref[...] = ri_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("t", "q_block", "k_block", "metric", "interpret"),
)
def centroid_topk(
    queries: jax.Array,  # [Q, D]
    centroids: jax.Array,  # [K, D]
    *,
    t: int,
    q_block: int = 128,
    k_block: int = 512,
    metric: str = "dot",
    interpret: bool = False,
):
    """Returns (values [Q, T] f32, ids [Q, T] int32)."""
    q, d = queries.shape
    k = centroids.shape[0]
    if q % q_block != 0:
        raise ValueError(f"Q={q} not a multiple of q_block={q_block}")
    if k % k_block != 0:
        raise ValueError(f"K={k} not a multiple of k_block={k_block}")
    if metric not in ("dot", "l2"):
        raise ValueError(metric)

    grid = (q // q_block, k // k_block)
    kern = functools.partial(_kernel, t=t, k_block=k_block, metric=metric)
    vals, ids = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((q_block, d), lambda qi, ki: (qi, 0)),
            pl.BlockSpec((k_block, d), lambda qi, ki: (ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((q_block, t), lambda qi, ki: (qi, 0)),
            pl.BlockSpec((q_block, t), lambda qi, ki: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q, t), jnp.float32),
            jax.ShapeDtypeStruct((q, t), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((q_block, t), jnp.float32),
            pltpu.VMEM((q_block, t), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(queries, centroids)
    return vals, ids
