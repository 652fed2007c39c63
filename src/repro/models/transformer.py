"""LM family: one parameterized decoder covering the five assigned archs.

  deepseek-v3-671b  — MLA attention, 1 shared + 256 routed top-8 (sigmoid
                      router, aux-loss-free bias), 3 dense lead layers, MTP
  deepseek-moe-16b  — MHA, 2 shared + 64 routed top-6, 1 dense lead layer
  gemma3-12b/27b    — GQA, 5:1 local:global sliding window, dual RoPE theta,
                      qk-norm, sandwich norms, tied embeddings
  chatglm3-6b       — 2-group MQA, partial rotary (0.5), SwiGLU, qkv bias

Layer stacks are lax.scan'ed over stacked parameters; the local/global
pattern is a per-layer *window array* (one HLO shape for both kinds), and the
gemma3 dual-theta RoPE is a per-layer select between two precomputed tables.
MoE layers run expert-parallel via shard_map when a mesh is supplied and
single-device otherwise (same math; see models/moe.py).

Sharding (Megatron TP on "model", DP on ("pod","data")):
  embed [V, D]            P(model, -)     vocab-parallel
  wq/wk/wv, w_gate/w_up   P(-, model)     column-parallel
  wo, w_down              P(model, -)     row-parallel
  experts [E, ...]        P(model, -, -)  expert-parallel
  activations [B, S, D]   P(dp, -, -)
  logits [B, S, V]        P(dp, -, model) (loss reduces over sharded V)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.models import moe as moe_lib
from repro.models.config import TransformerConfig
from repro.models.layers import (
    apply_rope,
    flash_attention,
    gated_mlp,
    rms_norm,
    rope_tables,
)

Array = jax.Array


# ------------------------------------------------------------------ init ---
def _init(key, shape, dtype, stddev=0.02):
    return jax.nn.initializers.truncated_normal(stddev=stddev)(
        key, shape, dtype
    )


def _init_attn(key, cfg: TransformerConfig, n_layers: int) -> Dict[str, Array]:
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ks = iter(jax.random.split(key, 16))
    dt = cfg.dtype
    L = n_layers
    if cfg.mla is not None:
        m = cfg.mla
        qk = m.qk_nope_dim + m.qk_rope_dim
        p = {
            "wq_a": _init(next(ks), (L, d, m.q_lora_rank), dt),
            "q_ln": jnp.zeros((L, m.q_lora_rank), dt),
            "wq_b": _init(next(ks), (L, m.q_lora_rank, h * qk), dt),
            "wkv_a": _init(next(ks), (L, d, m.kv_lora_rank + m.qk_rope_dim), dt),
            "kv_ln": jnp.zeros((L, m.kv_lora_rank), dt),
            "wk_b": _init(next(ks), (L, m.kv_lora_rank, h, m.qk_nope_dim), dt),
            "wv_b": _init(next(ks), (L, m.kv_lora_rank, h, m.v_head_dim), dt),
            "wo": _init(next(ks), (L, h * m.v_head_dim, d), dt),
        }
    else:
        p = {
            "wq": _init(next(ks), (L, d, h * dh), dt),
            "wk": _init(next(ks), (L, d, hkv * dh), dt),
            "wv": _init(next(ks), (L, d, hkv * dh), dt),
            "wo": _init(next(ks), (L, h * dh, d), dt),
        }
        if cfg.qkv_bias:
            p["bq"] = jnp.zeros((L, h * dh), dt)
            p["bk"] = jnp.zeros((L, hkv * dh), dt)
            p["bv"] = jnp.zeros((L, hkv * dh), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((L, cfg.d_head if cfg.mla is None else
                                 cfg.mla.qk_nope_dim + cfg.mla.qk_rope_dim), dt)
        p["k_norm"] = jnp.zeros_like(p["q_norm"])
    return p


def _init_block(key, cfg: TransformerConfig, n_layers: int, d_ff: int,
                is_moe: bool) -> Dict[str, Any]:
    ks = iter(jax.random.split(key, 8))
    d, dt, L = cfg.d_model, cfg.dtype, n_layers
    blk: Dict[str, Any] = {
        "ln1": jnp.zeros((L, d), dt),
        "ln2": jnp.zeros((L, d), dt),
        "attn": _init_attn(next(ks), cfg, L),
    }
    if cfg.sandwich_norm:
        blk["ln1_post"] = jnp.zeros((L, d), dt)
        blk["ln2_post"] = jnp.zeros((L, d), dt)
    if is_moe:
        moe_keys = jax.random.split(next(ks), L)
        per_layer = [
            moe_lib.init_moe_params(k, d, cfg.moe, dt) for k in moe_keys
        ]
        blk["moe"] = jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)
    else:
        blk["mlp"] = {
            "wg": _init(next(ks), (L, d, d_ff), dt),
            "wu": _init(next(ks), (L, d, d_ff), dt),
            "wd": _init(next(ks), (L, d_ff, d), dt),
        }
    return blk


def init_params(key: Array, cfg: TransformerConfig) -> Dict[str, Any]:
    ks = iter(jax.random.split(key, 8))
    d = cfg.d_model
    params: Dict[str, Any] = {
        "embed": _init(next(ks), (cfg.vocab_size, d), cfg.dtype),
        "final_norm": jnp.zeros((d,), cfg.dtype),
    }
    d_ff_dense = cfg.moe.d_ff_dense if cfg.moe else cfg.d_ff
    if cfg.n_dense_layers:
        params["blocks"] = _init_block(
            next(ks), cfg, cfg.n_dense_layers, d_ff_dense, is_moe=False
        )
    if cfg.n_moe_layers:
        params["moe_blocks"] = _init_block(
            next(ks), cfg, cfg.n_moe_layers, 0, is_moe=True
        )
    if not cfg.tie_embeddings:
        params["lm_head"] = _init(next(ks), (d, cfg.vocab_size), cfg.dtype)
    if cfg.mtp_depth:
        mtp_blk = _init_block(next(ks), cfg, 1, d_ff_dense, is_moe=False)
        params["mtp"] = {
            "norm_h": jnp.zeros((d,), cfg.dtype),
            "norm_e": jnp.zeros((d,), cfg.dtype),
            "proj": _init(next(ks), (2 * d, d), cfg.dtype),
            "block": mtp_blk,
            "final_norm": jnp.zeros((d,), cfg.dtype),
        }
    return params


# ------------------------------------------------------------- shardings ---
def param_pspecs(cfg: TransformerConfig) -> Dict[str, Any]:
    """PartitionSpec tree matching init_params (leading L axis unsharded).

    TP ("model") on head/ff/expert dims; FSDP (``cfg.fsdp_axis``) on the
    other weight dim so per-chip parameter bytes scale 1/(TP·FSDP) — without
    it a 671B model stores 84 GB/chip (model-sharding only) and cannot fit
    v5e.  XLA re-gathers the FSDP shards per layer inside the scan (the
    classic ZeRO-3 all-gather, visible in the collective term).  Experts
    shard over ``cfg.moe_ep_axes``.  Multi-pod keeps one replica per pod
    ("pod" carries pure DP).
    """
    f = cfg.fsdp_axis
    ep = cfg.moe_ep_axes
    col = P(None, f, "model")  # [L, D, F]
    row = P(None, "model", f)  # [L, F, D]
    rep1 = P(None, None)  # [L, D]
    if cfg.mla is not None:
        attn = {
            "wq_a": P(None, f, None),
            "q_ln": rep1,
            "wq_b": P(None, f, "model"),
            "wkv_a": P(None, f, None),
            "kv_ln": rep1,
            "wk_b": P(None, f, "model", None),
            "wv_b": P(None, f, "model", None),
            "wo": row,
        }
    else:
        attn = {"wq": col, "wk": col, "wv": col, "wo": row}
        if cfg.qkv_bias:
            attn.update({"bq": P(None, "model"), "bk": P(None, "model"),
                         "bv": P(None, "model")})
    if cfg.qk_norm:
        attn["q_norm"] = rep1
        attn["k_norm"] = rep1

    def block_specs(is_moe):
        b = {"ln1": rep1, "ln2": rep1, "attn": dict(attn)}
        if cfg.sandwich_norm:
            b["ln1_post"] = rep1
            b["ln2_post"] = rep1
        if is_moe:
            b["moe"] = {
                "router": P(None, f, None),
                "router_bias": P(None, None),
                "wg": P(None, ep, f, None),
                "wu": P(None, ep, f, None),
                "wd": P(None, ep, None, f),
            }
            if cfg.moe.n_shared:
                b["moe"].update({
                    "shared_wg": col, "shared_wu": col, "shared_wd": row,
                })
        else:
            b["mlp"] = {"wg": col, "wu": col, "wd": row}
        return b

    specs: Dict[str, Any] = {
        "embed": P("model", f),
        "final_norm": P(None),
    }
    if cfg.n_dense_layers:
        specs["blocks"] = block_specs(False)
    if cfg.n_moe_layers:
        specs["moe_blocks"] = block_specs(True)
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(f, "model")
    if cfg.mtp_depth:
        specs["mtp"] = {
            "norm_h": P(None),
            "norm_e": P(None),
            "proj": P(f, None),
            "block": block_specs(False),
            "final_norm": P(None),
        }
    return specs


# --------------------------------------------------------------- forward ---
def _head_constrain(t, mesh, dp_axes, n_heads):
    """Pin expanded q/k/v to the head-sharded TP layout.

    Without this, XLA resolving the SP (S-sharded) ↔ TP (head-sharded)
    boundary can replicate the EXPANDED attention tensors — measured 62
    GB/layer/chip of f32 full-head all-gathers on deepseek-v3 (EXPERIMENTS
    §Perf iter 1). KV heads that don't divide the axis stay replicated.
    """
    if mesh is None or n_heads % mesh.shape["model"] != 0:
        return t
    return jax.lax.with_sharding_constraint(
        t, P(dp_axes, None, "model", None)
    )


def _gqa_attention(x, p, cfg: TransformerConfig, sin, cos, window,
                   q_offset=0, mesh=None, dp_axes=("data",)):
    b, s, d = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = _head_constrain(q.reshape(b, s, h, dh), mesh, dp_axes, h)
    k = _head_constrain(k.reshape(b, s, hkv, dh), mesh, dp_axes, hkv)
    v = _head_constrain(v.reshape(b, s, hkv, dh), mesh, dp_axes, hkv)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    rd = int(dh * cfg.rotary_pct)
    q = apply_rope(q, sin, cos, rd)
    k = apply_rope(k, sin, cos, rd)
    out = flash_attention(
        q, k, v, window=window, q_offset=q_offset,
        block_k=min(cfg.attn_block_k, s),
    )
    return out.reshape(b, s, h * dh) @ p["wo"], (k, v)


def _mla_attention(x, p, cfg: TransformerConfig, sin, cos, window,
                   q_offset=0, mesh=None, dp_axes=("data",)):
    """MLA training/prefill path (expanded); decode uses the absorbed path."""
    m = cfg.mla
    b, s, d = x.shape
    h = cfg.n_heads
    qk = m.qk_nope_dim + m.qk_rope_dim
    cq = rms_norm(x @ p["wq_a"], p["q_ln"], cfg.norm_eps)
    q = (cq @ p["wq_b"]).reshape(b, s, h, qk)
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim :]

    ckv_full = x @ p["wkv_a"]  # [B,S,kvr+rope]
    c_kv = rms_norm(ckv_full[..., : m.kv_lora_rank], p["kv_ln"], cfg.norm_eps)
    k_rope = ckv_full[..., m.kv_lora_rank :][:, :, None, :]  # [B,S,1,rope]

    q_rope = apply_rope(q_rope, sin, cos)
    k_rope = apply_rope(k_rope, sin, cos)

    k_nope = jnp.einsum("bsr,rhd->bshd", c_kv, p["wk_b"])
    v = jnp.einsum("bsr,rhd->bshd", c_kv, p["wv_b"])
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, s, h, m.qk_rope_dim))], -1
    )
    q_full = jnp.concatenate([q_nope, q_rope], -1)
    # keep the EXPANDED tensors head-sharded (they are 128-head wide; letting
    # XLA replicate them costs tens of GB/layer — §Perf iter 1)
    q_full = _head_constrain(q_full, mesh, dp_axes, h)
    k = _head_constrain(k, mesh, dp_axes, h)
    v = _head_constrain(v, mesh, dp_axes, h)
    out = flash_attention(
        q_full, k, v, window=window, q_offset=q_offset,
        block_k=min(cfg.attn_block_k, s), softmax_scale=qk ** -0.5,
    )
    out = out.reshape(b, s, h * m.v_head_dim) @ p["wo"]
    # cache payload for prefill: the latent pair (what MLA stores)
    return out, (c_kv, k_rope[:, :, 0, :])


def _moe_ffn(x, moe_params, cfg: TransformerConfig,
             mesh: Optional[Mesh], dp_axes: Tuple[str, ...]):
    """Expert FFN + shared expert. x: [B, S, D].

    Parallelism plan from cfg: experts sharded over ``moe_ep_axes`` (EP);
    when ``fsdp_axis`` is set the expert weights are additionally stored
    FSDP-sharded and all-gathered INSIDE the scan/remat body so the gather
    can never be hoisted into a whole-stack materialization (ZeRO-3: a
    layer's gathered weights live only for that layer).  If EP uses an axis
    that also carries data parallelism ("data" at decode), activations are
    replicated over it (token batches at decode are KiB-scale).
    """
    b, s, d = x.shape
    mcfg = cfg.moe
    ep_axes = cfg.moe_ep_axes if mesh is not None else ()
    fsdp = cfg.fsdp_axis if mesh is not None else None
    # reduce-scatter combine is valid when EP is the single "model" axis and
    # the sequence divides it (not decode S=1, not multi-axis EP)
    use_scatter = (
        cfg.moe_combine == "scatter" and mesh is not None
        and ep_axes == ("model",) and s % mesh.shape["model"] == 0
    )

    def local(xl, wg, wu, wd, router, rbias):
        if fsdp is not None:
            wg = jax.lax.all_gather(wg, fsdp, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, fsdp, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, fsdp, axis=2, tiled=True)
        n = xl.shape[0] * xl.shape[1]
        flat = xl.reshape(n, d)
        p_local = {"wg": wg, "wu": wu, "wd": wd, "router": router,
                   "router_bias": rbias}
        out, metrics = moe_lib.moe_ffn_local(
            flat, p_local, mcfg,
            ep_axes=ep_axes if mesh is not None else (),
            act=cfg.act,
            combine=not use_scatter,
        )
        if use_scatter:  # combine partial expert outputs into the SP layout
            out = out.reshape(xl.shape)
            out = jax.lax.psum_scatter(out, "model", scatter_dimension=1,
                                       tiled=True)
            metrics["n_dropped"] = jax.lax.psum(metrics["n_dropped"], "model")
            return out, metrics["aux_loss"], metrics["n_dropped"]
        return out.reshape(xl.shape), metrics["aux_loss"], metrics["n_dropped"]

    if mesh is None:
        out, aux, dropped = local(
            x, moe_params["wg"], moe_params["wu"], moe_params["wd"],
            moe_params["router"], moe_params["router_bias"],
        )
    else:
        # tokens must be replicated over any EP axis that is also a dp axis
        dp_eff = tuple(a for a in dp_axes if a not in ep_axes)
        dp = P(dp_eff if dp_eff else None, None, None)
        out_spec = (P(dp_eff if dp_eff else None, "model", None)
                    if use_scatter else dp)
        out, aux, dropped = jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(dp, P(ep_axes, fsdp, None), P(ep_axes, fsdp, None),
                      P(ep_axes, None, fsdp), P(None, None), P(None)),
            out_specs=(out_spec, P(), P()),
            check_vma=False,
        )(x, moe_params["wg"], moe_params["wu"], moe_params["wd"],
          moe_params["router"], moe_params["router_bias"])
    if mcfg.n_shared:
        out = out + gated_mlp(
            x, moe_params["shared_wg"], moe_params["shared_wu"],
            moe_params["shared_wd"], cfg.act,
        )
    return out, aux, dropped


def _block_apply(h, blk_params, cfg: TransformerConfig, sin, cos, window,
                 is_moe: bool, mesh, dp_axes, q_offset=0):
    """One transformer block. Returns (h, kv_payload, aux, dropped).

    Under a mesh the carry is kept SEQUENCE-SHARDED over "model" (Megatron
    SP): the per-layer residual the remat policy must keep alive shrinks by
    the TP width (61 × 470 MB → 61 × 29 MB for deepseek-v3 train_4k), and
    XLA inserts the all-gather (entering attention) / reduce-scatter
    (leaving wo / w_down) pairs around each block.  Sq=1 decode skips SP.
    """
    if mesh is not None and h.shape[1] % mesh.shape["model"] == 0:
        h = jax.lax.with_sharding_constraint(h, P(dp_axes, "model", None))
    attn_in = rms_norm(h, blk_params["ln1"], cfg.norm_eps)
    attn_fn = _mla_attention if cfg.mla is not None else _gqa_attention
    attn_out, kv = attn_fn(attn_in, blk_params["attn"], cfg, sin, cos,
                           window, q_offset, mesh, dp_axes)
    if cfg.sandwich_norm:
        attn_out = rms_norm(attn_out, blk_params["ln1_post"], cfg.norm_eps)
    h = h + attn_out

    mlp_in = rms_norm(h, blk_params["ln2"], cfg.norm_eps)
    if is_moe:
        mlp_out, aux, dropped = _moe_ffn(mlp_in, blk_params["moe"], cfg,
                                         mesh, dp_axes)
    else:
        mlp_out = gated_mlp(mlp_in, blk_params["mlp"]["wg"],
                            blk_params["mlp"]["wu"],
                            blk_params["mlp"]["wd"], cfg.act)
        aux = jnp.zeros((), jnp.float32)
        dropped = jnp.zeros((), jnp.int32)
    if cfg.sandwich_norm:
        mlp_out = rms_norm(mlp_out, blk_params["ln2_post"], cfg.norm_eps)
    return h + mlp_out, kv, aux, dropped


def _scan_stack(h, stack, cfg, windows, sin_l, cos_l, sin_g, cos_g,
                is_moe, mesh, dp_axes, collect_kv=False, q_offset=0):
    """lax.scan over a stacked block. windows: [L] int32 per-layer."""

    def apply(hc, blk, sin, cos, w):
        return _block_apply(hc, blk, cfg, sin, cos, w, is_moe, mesh,
                            dp_axes, q_offset)

    if cfg.remat:
        apply = jax.checkpoint(
            apply, policy=jax.checkpoint_policies.nothing_saveable
        )

    def body(carry, xs):
        hc = carry
        blk, w = xs
        is_global = w == 0
        sin = jnp.where(is_global, sin_g, sin_l)
        cos = jnp.where(is_global, cos_g, cos_l)
        h2, kv, aux, dropped = apply(hc, blk, sin, cos, w)
        ys = (kv if collect_kv else None, aux, dropped)
        return h2, ys

    h, (kv, aux, dropped) = jax.lax.scan(
        body, h, (stack, windows),
        unroll=windows.shape[0] if cfg.scan_unroll else 1,
    )
    return h, kv, jnp.sum(aux), jnp.sum(dropped)


def forward(
    params: Dict[str, Any],
    cfg: TransformerConfig,
    tokens: Array,  # [B, S] int32
    *,
    mesh: Optional[Mesh] = None,
    dp_axes: Tuple[str, ...] = ("data",),
    collect_kv: bool = False,
    q_offset: int = 0,
) -> Tuple[Array, Dict[str, Any]]:
    """Full-sequence forward. Returns (hidden [B,S,D], aux dict).

    aux carries moe metrics and (if collect_kv) the per-layer cache payloads
    for prefill.
    """
    b, s = tokens.shape
    constrain = (
        (lambda x, spec: jax.lax.with_sharding_constraint(x, P(*spec)))
        if mesh is not None else (lambda x, spec: x)
    )
    h = jnp.take(params["embed"], tokens, axis=0)  # [B,S,D]
    if cfg.scale_embed:
        h = h * jnp.asarray(np.sqrt(cfg.d_model), h.dtype)
    sp_ok = mesh is not None and s % mesh.shape["model"] == 0
    h = constrain(h, (dp_axes, "model" if sp_ok else None, None))

    positions = q_offset + jnp.arange(s)
    rd = (cfg.mla.qk_rope_dim if cfg.mla is not None
          else int(cfg.d_head * cfg.rotary_pct))
    sin_l, cos_l = rope_tables(positions, rd, cfg.rope_theta)
    theta_g = cfg.rope_theta_global or cfg.rope_theta
    sin_g, cos_g = rope_tables(positions, rd, theta_g)

    wp = cfg.window_pattern()
    aux: Dict[str, Any] = {}
    kv_all = []
    if cfg.n_dense_layers:
        w_dense = jnp.asarray(wp[: cfg.n_dense_layers])
        h, kv, aux_l, drop = _scan_stack(
            h, params["blocks"], cfg, w_dense, sin_l, cos_l, sin_g, cos_g,
            False, mesh, dp_axes, collect_kv, q_offset,
        )
        kv_all.append(kv)
        aux["moe_aux_loss"] = aux_l
        aux["moe_dropped"] = drop
    if cfg.n_moe_layers:
        w_moe = jnp.asarray(wp[cfg.n_dense_layers :])
        h, kv, aux_l, drop = _scan_stack(
            h, params["moe_blocks"], cfg, w_moe, sin_l, cos_l, sin_g, cos_g,
            True, mesh, dp_axes, collect_kv, q_offset,
        )
        kv_all.append(kv)
        aux["moe_aux_loss"] = aux.get("moe_aux_loss", 0.0) + aux_l
        aux["moe_dropped"] = aux.get("moe_dropped", 0) + drop
    h = constrain(h, (dp_axes, "model" if sp_ok else None, None))
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    if collect_kv:
        aux["kv"] = kv_all
    return h, aux


def logits_from_hidden(params, cfg, h, constrain=None):
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = h @ head.astype(h.dtype)
    if cfg.logit_softcap > 0:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits / c)
    if constrain is not None:
        logits = constrain(logits)
    return logits


def lm_loss(
    params: Dict[str, Any],
    cfg: TransformerConfig,
    tokens: Array,  # [B, S]
    labels: Array,  # [B, S] (-1 = ignore)
    *,
    mesh: Optional[Mesh] = None,
    dp_axes: Tuple[str, ...] = ("data",),
) -> Tuple[Array, Dict[str, Any]]:
    """Causal LM loss (+ MTP auxiliary loss + MoE balance loss)."""
    sp_ok = mesh is not None and tokens.shape[1] % mesh.shape["model"] == 0
    constrain = (
        (lambda x: jax.lax.with_sharding_constraint(
            x, P(dp_axes, "model" if sp_ok else None, None)))
        if mesh is not None else None
    )
    h, aux = forward(params, cfg, tokens, mesh=mesh, dp_axes=dp_axes)

    def ce(hid, lab):
        lg = logits_from_hidden(params, cfg, hid, constrain)
        lg = lg.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(
            lg, jnp.maximum(lab, 0)[..., None], axis=-1
        )[..., 0]
        mask = (lab >= 0).astype(jnp.float32)
        return jnp.sum((lse - gold) * mask), jnp.sum(mask)

    total, denom = ce(h, labels)
    metrics = {"ce_tokens": denom}

    if cfg.mtp_depth:
        # predict t+2: combine h_t with embedding of token t+1 (=labels_t)
        mtp = params["mtp"]
        nxt = jnp.maximum(labels, 0)
        e_next = jnp.take(params["embed"], nxt, axis=0)
        comb = jnp.concatenate(
            [rms_norm(h, mtp["norm_h"], cfg.norm_eps),
             rms_norm(e_next, mtp["norm_e"], cfg.norm_eps)], -1
        ) @ mtp["proj"]
        blk = jax.tree.map(lambda x: x[0], mtp["block"])  # unstack L=1
        s = comb.shape[1]
        rd = (cfg.mla.qk_rope_dim if cfg.mla is not None
              else int(cfg.d_head * cfg.rotary_pct))
        sin, cos = rope_tables(jnp.arange(s), rd, cfg.rope_theta)
        h2, _kv, _aux, _drop = _block_apply(
            comb, blk, cfg, sin, cos, jnp.int32(0), False, mesh, dp_axes
        )
        h2 = rms_norm(h2, mtp["final_norm"], cfg.norm_eps)
        labels_mtp = jnp.concatenate(
            [labels[:, 1:], jnp.full_like(labels[:, :1], -1)], axis=1
        )
        mtp_total, mtp_denom = ce(h2, labels_mtp)
        total = total + cfg.mtp_loss_weight * mtp_total
        denom = denom  # main-token normalization
        metrics["mtp_tokens"] = mtp_denom

    loss = total / jnp.maximum(denom, 1.0)
    if cfg.moe is not None:
        loss = loss + cfg.moe.aux_loss_coef * aux.get("moe_aux_loss", 0.0)
        metrics["moe_aux_loss"] = aux.get("moe_aux_loss", 0.0)
        metrics["moe_dropped"] = aux.get("moe_dropped", 0)
    return loss, metrics
