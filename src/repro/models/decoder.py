"""Unified decoder-only backbone (dense / MoE / VLM families).

Scan-over-layers with stacked parameters (HLO size independent of depth),
optional leading dense layers (Kimi-K2 ``first_k_dense``), optional visual
token injection (InternVL2), GQA attention with optional sliding window and
QKV bias, RoPE, SwiGLU or MoE FFN, vocab-parallel logits.

Three entry points per the model API: ``forward`` (train), ``prefill``
(logits + filled KV cache), ``decode_step`` (one token against a cache).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.dist.sharding import decl, stack
from repro.models import attention as attn
from repro.models import kvcache as kvc
from repro.models.layers import (embed_decl, embed_lookup, logits_out,
                                 rmsnorm, rmsnorm_decl, swiglu, swiglu_decl)
from repro.models.moe import moe_apply, moe_decl


# --------------------------------------------------------------------------
# Declarations
# --------------------------------------------------------------------------

def _layer_decl(cfg: ArchConfig, kind: str):
    d = {
        "ln1": rmsnorm_decl(cfg.d_model),
        "attn": attn.attention_decl(cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                    cfg.head_dim, cfg.qkv_bias),
        "ln2": rmsnorm_decl(cfg.d_model),
    }
    if kind == "moe":
        d["moe"] = moe_decl(cfg)
    else:
        ff = cfg.moe.d_ff_dense if (cfg.moe and cfg.moe.first_k_dense) else cfg.d_ff
        d["mlp"] = swiglu_decl(cfg.d_model, ff)
    return d


def n_dense_layers(cfg: ArchConfig) -> int:
    return cfg.moe.first_k_dense if cfg.moe else 0


def param_decls(cfg: ArchConfig):
    decls = {
        "embed": embed_decl(cfg.vocab, cfg.d_model),
        "final_norm": rmsnorm_decl(cfg.d_model),
    }
    nd = n_dense_layers(cfg)
    if nd:
        decls["dense_layers"] = stack(_layer_decl(cfg, "dense"), nd)
    kind = "moe" if cfg.moe else "dense"
    decls["layers"] = stack(_layer_decl(cfg, kind), cfg.n_layers - nd)
    if cfg.family == "vlm":
        fe = cfg.frontend
        decls["vis_proj"] = {
            "w": decl((fe.feat_dim, cfg.d_model), ("mlp", "embed")),
            "norm": rmsnorm_decl(fe.feat_dim),
        }
    return decls


def cache_decl(cfg: ArchConfig, batch: int, cache_len: int):
    return kvc.kv_cache_decl(cfg.n_layers, batch, cache_len,
                             cfg.n_kv_heads, cfg.head_dim)


# --------------------------------------------------------------------------
# Layer application
# --------------------------------------------------------------------------

def _ffn(cfg: ArchConfig, lp, x, kind: str):
    with jax.named_scope("mlp"):
        if kind == "moe":
            return moe_apply(cfg, lp["moe"], x)
        return swiglu(lp["mlp"], x), jnp.float32(0.0)


def _apply_layer(cfg: ArchConfig, lp, x, positions, kind: str,
                 return_kv: bool = False):
    h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
    with jax.named_scope("attention"):
        q, k, v = attn.project_qkv(lp["attn"], h, positions, cfg.rope_theta)
        o = attn.attention(q, k, v, positions, positions, causal=True,
                           window=cfg.window, chunk=cfg.attn_chunk,
                           chunk_threshold=cfg.attn_chunk_threshold)
        x = x + attn.project_out(lp["attn"], o)
    h2 = rmsnorm(lp["ln2"], x, cfg.norm_eps)
    y, aux = _ffn(cfg, lp, h2, kind)
    x = x + y
    if return_kv:
        return x, aux, (k, v)
    return x, aux


def _apply_layer_decode(cfg: ArchConfig, lp, x, k_l, v_l, kv_pos, pos,
                        slot, kind: str):
    """x: (B,1,D); k_l/v_l: (B,S,K,hd); pos: (B,)."""
    h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
    q, k, v = attn.project_qkv(lp["attn"], h, pos[:, None], cfg.rope_theta)
    k_l, v_l = kvc.update_kv_layer(k_l, v_l, k, v, slot)
    o = attn.decode_attention(q, k_l, v_l, kv_pos, pos, window=cfg.window)
    x = x + attn.project_out(lp["attn"], o)
    h2 = rmsnorm(lp["ln2"], x, cfg.norm_eps)
    y, _ = _ffn(cfg, lp, h2, kind)
    return x + y, k_l, v_l


# --------------------------------------------------------------------------
# Embedding (with optional modality injection)
# --------------------------------------------------------------------------

def _embed_inputs(cfg: ArchConfig, params, batch):
    x = embed_lookup(params["embed"], batch["tokens"])
    if cfg.family == "vlm" and "patches" in batch:
        vp = params["vis_proj"]
        vis = rmsnorm(vp["norm"], batch["patches"], cfg.norm_eps)
        vis = jnp.einsum("bpf,fd->bpd", vis, vp["w"]).astype(x.dtype)
        n = vis.shape[1]
        x = jnp.concatenate([vis, x[:, n:]], axis=1)  # patches fill the front
    return x


# --------------------------------------------------------------------------
# Entry points
# --------------------------------------------------------------------------

def _scan_layers(cfg: ArchConfig, stacked, x, positions, kind: str,
                 collect_kv: bool):
    def body(carry, lp):
        x, aux = carry
        if collect_kv:
            x, a, kv = _apply_layer(cfg, lp, x, positions, kind, True)
            return (x, aux + a), kv
        x, a = _apply_layer(cfg, lp, x, positions, kind)
        return (x, aux + a), None

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    return jax.lax.scan(body, (x, jnp.float32(0.0)), stacked)


def forward(cfg: ArchConfig, params, batch):
    """Full-sequence forward -> (logits (B,S,V), aux_loss)."""
    x = _embed_inputs(cfg, params, batch)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    aux = jnp.float32(0.0)
    if n_dense_layers(cfg):
        (x, a), _ = _scan_layers(cfg, params["dense_layers"], x, positions,
                                 "dense", False)
        aux += a
    kind = "moe" if cfg.moe else "dense"
    (x, a), _ = _scan_layers(cfg, params["layers"], x, positions, kind, False)
    aux += a
    with jax.named_scope("lm_head"):
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return logits_out(params["embed"], x), aux


def prefill(cfg: ArchConfig, params, batch):
    """-> (last-token logits (B,V), cache)."""
    x = _embed_inputs(cfg, params, batch)
    B, S = x.shape[0], x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    kvs = []
    aux = jnp.float32(0.0)
    if n_dense_layers(cfg):
        (x, a), kv = _scan_layers(cfg, params["dense_layers"], x, positions,
                                  "dense", True)
        kvs.append(kv)
        aux += a
    kind = "moe" if cfg.moe else "dense"
    (x, a), kv = _scan_layers(cfg, params["layers"], x, positions, kind, True)
    kvs.append(kv)
    k = jnp.concatenate([kv[0] for kv in kvs], axis=0) if len(kvs) > 1 else kvs[0][0]
    v = jnp.concatenate([kv[1] for kv in kvs], axis=0) if len(kvs) > 1 else kvs[0][1]
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = logits_out(params["embed"], x[:, -1])
    cache = {"k": k, "v": v, "kv_pos": kvc.prefilled_pos(B, S)}
    return logits, cache


def decode_step(cfg: ArchConfig, params, cache, batch):
    """batch: {"token": (B,1) int32, "pos": (B,) int32} -> (logits, cache)."""
    token, pos = batch["token"], batch["pos"]
    x = embed_lookup(params["embed"], token)
    cache_len = cache["k"].shape[2]
    slot = kvc.cache_slot(pos, cache_len)
    kv_pos = kvc.update_kv_pos(cache["kv_pos"], pos, cache_len)

    # Leading dense layers (Kimi first_k_dense) are processed eagerly —
    # a single scan can't mix layer pytrees of different structure.
    nd = n_dense_layers(cfg)
    kind = "moe" if cfg.moe else "dense"

    def body_uniform(x, xs):
        lp, k_l, v_l = xs
        x, k_l, v_l = _apply_layer_decode(cfg, lp, x, k_l, v_l, kv_pos, pos,
                                          slot, kind)
        return x, (k_l, v_l)

    if nd:
        new_dense = []
        for i in range(nd):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["dense_layers"])
            x, k_i, v_i = _apply_layer_decode(
                cfg, lp, x, cache["k"][i], cache["v"][i], kv_pos, pos, slot,
                "dense")
            new_dense.append((k_i, v_i))
        x, (k_rest, v_rest) = jax.lax.scan(
            body_uniform, x, (params["layers"], cache["k"][nd:], cache["v"][nd:]))
        k_new = jnp.concatenate([jnp.stack([kv[0] for kv in new_dense]), k_rest], 0)
        v_new = jnp.concatenate([jnp.stack([kv[1] for kv in new_dense]), v_rest], 0)
    else:
        x, (k_new, v_new) = jax.lax.scan(
            body_uniform, x, (params["layers"], cache["k"], cache["v"]))

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = logits_out(params["embed"], x[:, -1])
    return logits, {"k": k_new, "v": v_new, "kv_pos": kv_pos}
