"""Encoder-decoder backbone (Whisper-small).

The audio frontend is a STUB per the assignment: ``input_specs`` feeds
precomputed conv-frame embeddings (B, n_frames, feat_dim).  The backbone is
faithful to Whisper's shape (LayerNorm + GELU MLP, MHA); positions use RoPE
in place of Whisper's learned/sinusoidal tables (deviation noted in
DESIGN.md — keeps parameters independent of sequence length).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.dist.sharding import decl, stack
from repro.models import attention as attn
from repro.models import kvcache as kvc
from repro.models.layers import (embed_decl, embed_lookup, gelu_mlp,
                                 gelu_mlp_decl, layernorm, layernorm_decl,
                                 logits_out)


def _enc_layer_decl(cfg: ArchConfig):
    return {
        "ln1": layernorm_decl(cfg.d_model),
        "attn": attn.attention_decl(cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                    cfg.head_dim),
        "ln2": layernorm_decl(cfg.d_model),
        "mlp": gelu_mlp_decl(cfg.d_model, cfg.d_ff),
    }


def _dec_layer_decl(cfg: ArchConfig):
    d = _enc_layer_decl(cfg)
    d["ln_x"] = layernorm_decl(cfg.d_model)
    d["cross"] = attn.attention_decl(cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                     cfg.head_dim)
    return d


def param_decls(cfg: ArchConfig):
    fe = cfg.frontend
    return {
        "enc_in": {"w": decl((fe.feat_dim, cfg.d_model), (None, "embed"))},
        "enc_layers": stack(_enc_layer_decl(cfg), cfg.n_enc_layers),
        "enc_norm": layernorm_decl(cfg.d_model),
        "embed": embed_decl(cfg.vocab, cfg.d_model),
        "dec_layers": stack(_dec_layer_decl(cfg), cfg.n_layers),
        "final_norm": layernorm_decl(cfg.d_model),
    }


def cache_decl(cfg: ArchConfig, batch: int, cache_len: int):
    d = kvc.kv_cache_decl(cfg.n_layers, batch, cache_len, cfg.n_kv_heads,
                          cfg.head_dim)
    d.update(kvc.kv_cache_decl(cfg.n_layers, batch, cfg.frontend.n_tokens,
                               cfg.n_kv_heads, cfg.head_dim, prefix="cross_"))
    del d["cross_kv_pos"]
    return d


# --------------------------------------------------------------------------

def encode(cfg: ArchConfig, params, frames):
    x = jnp.einsum("bsf,fd->bsd", frames.astype(jnp.bfloat16), params["enc_in"]["w"])
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)

    def body(x, lp):
        h = layernorm(lp["ln1"], x, cfg.norm_eps)
        q, k, v = attn.project_qkv(lp["attn"], h, positions, cfg.rope_theta)
        o = attn.attention(q, k, v, positions, positions, causal=False,
                           chunk=cfg.attn_chunk,
                           chunk_threshold=cfg.attn_chunk_threshold)
        x = x + attn.project_out(lp["attn"], o)
        h = layernorm(lp["ln2"], x, cfg.norm_eps)
        return x + gelu_mlp(lp["mlp"], h), None

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return layernorm(params["enc_norm"], x, cfg.norm_eps)


def _cross_attend(cfg, lp, x, mem_k, mem_v, dec_pos, enc_pos):
    h = layernorm(lp["ln_x"], x, cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, lp["cross"]["wq"])
    o = attn.attention(q, mem_k, mem_v, dec_pos, enc_pos, causal=False,
                       chunk=cfg.attn_chunk,
                       chunk_threshold=cfg.attn_chunk_threshold)
    return x + attn.project_out(lp["cross"], o)


def _cross_kv(lp, mem):
    k = jnp.einsum("bsd,dhk->bshk", mem, lp["cross"]["wk"])
    v = jnp.einsum("bsd,dhk->bshk", mem, lp["cross"]["wv"])
    return k, v


def _dec_layer(cfg, lp, x, mem, positions, enc_pos, collect_kv=False):
    h = layernorm(lp["ln1"], x, cfg.norm_eps)
    q, k, v = attn.project_qkv(lp["attn"], h, positions, cfg.rope_theta)
    o = attn.attention(q, k, v, positions, positions, causal=True,
                       chunk=cfg.attn_chunk,
                       chunk_threshold=cfg.attn_chunk_threshold)
    x = x + attn.project_out(lp["attn"], o)
    mk, mv = _cross_kv(lp, mem)
    x = _cross_attend(cfg, lp, x, mk, mv, positions, enc_pos)
    h = layernorm(lp["ln2"], x, cfg.norm_eps)
    x = x + gelu_mlp(lp["mlp"], h)
    if collect_kv:
        return x, (k, v, mk, mv)
    return x, None


def forward(cfg: ArchConfig, params, batch):
    mem = encode(cfg, params, batch["frames"])
    x = embed_lookup(params["embed"], batch["tokens"])
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    enc_pos = jnp.arange(mem.shape[1], dtype=jnp.int32)

    def body(x, lp):
        return _dec_layer(cfg, lp, x, mem, positions, enc_pos)

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = jax.lax.scan(body, x, params["dec_layers"])
    with jax.named_scope("lm_head"):
        x = layernorm(params["final_norm"], x, cfg.norm_eps)
        return logits_out(params["embed"], x), jnp.float32(0.0)


def prefill(cfg: ArchConfig, params, batch):
    mem = encode(cfg, params, batch["frames"])
    x = embed_lookup(params["embed"], batch["tokens"])
    B, S = x.shape[0], x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    enc_pos = jnp.arange(mem.shape[1], dtype=jnp.int32)

    def body(x, lp):
        return _dec_layer(cfg, lp, x, mem, positions, enc_pos, collect_kv=True)

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, (k, v, mk, mv) = jax.lax.scan(body, x, params["dec_layers"])
    x = layernorm(params["final_norm"], x, cfg.norm_eps)
    logits = logits_out(params["embed"], x[:, -1])
    cache = {"k": k, "v": v, "kv_pos": kvc.prefilled_pos(B, S),
             "cross_k": mk, "cross_v": mv}
    return logits, cache


def decode_step(cfg: ArchConfig, params, cache, batch):
    token, pos = batch["token"], batch["pos"]
    x = embed_lookup(params["embed"], token)
    cache_len = cache["k"].shape[2]
    slot = kvc.cache_slot(pos, cache_len)
    kv_pos = kvc.update_kv_pos(cache["kv_pos"], pos, cache_len)
    enc_pos = jnp.arange(cache["cross_k"].shape[2], dtype=jnp.int32)

    def body(x, xs):
        lp, k_l, v_l, mk, mv = xs
        h = layernorm(lp["ln1"], x, cfg.norm_eps)
        q, k, v = attn.project_qkv(lp["attn"], h, pos[:, None], cfg.rope_theta)
        k_l, v_l = kvc.update_kv_layer(k_l, v_l, k, v, slot)
        o = attn.decode_attention(q, k_l, v_l, kv_pos, pos)
        x = x + attn.project_out(lp["attn"], o)
        x = _cross_attend(cfg, lp, x, mk, mv, pos[:, None], enc_pos)
        h = layernorm(lp["ln2"], x, cfg.norm_eps)
        x = x + gelu_mlp(lp["mlp"], h)
        return x, (k_l, v_l)

    x, (k_new, v_new) = jax.lax.scan(
        body, x, (params["dec_layers"], cache["k"], cache["v"],
                  cache["cross_k"], cache["cross_v"]))
    x = layernorm(params["final_norm"], x, cfg.norm_eps)
    logits = logits_out(params["embed"], x[:, -1])
    return logits, {"k": k_new, "v": v_new, "kv_pos": kv_pos,
                    "cross_k": cache["cross_k"], "cross_v": cache["cross_v"]}
