"""Hymba-style hybrid: parallel attention + SSM heads per layer.

Attention branch: GQA with sliding window + RoPE.  SSM branch: selective
state-space in SSD form (scalar per-head decay, state size ``ssm_state``) —
the TPU-friendly adaptation noted in DESIGN.md; it shares the chunked
linear-attention core (and the Pallas ssm_scan kernel) with RWKV6.
Branch outputs are averaged (Hymba's fused parallel heads), then SwiGLU MLP.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.dist.sharding import decl, stack
from repro.models import attention as attn
from repro.models import kvcache as kvc
from repro.models import linear_attn as la
from repro.models.layers import (embed_decl, embed_lookup, logits_out,
                                 rmsnorm, rmsnorm_decl, swiglu, swiglu_decl)

CONV_W = 3


def _dims(cfg: ArchConfig):
    H, hd, N = cfg.n_heads, cfg.head_dim, cfg.ssm_state
    return H, hd, N, H * hd


def _layer_decl(cfg: ArchConfig):
    D = cfg.d_model
    H, hd, N, Din = _dims(cfg)
    return {
        "ln1": rmsnorm_decl(D),
        "attn": attn.attention_decl(D, H, cfg.n_kv_heads, hd),
        "ssm": {
            "in_w": decl((D, H, hd), ("embed", "heads", None)),
            "z_w": decl((D, H, hd), ("embed", "heads", None)),
            "B_w": decl((D, H, N), ("embed", "heads", None)),
            "C_w": decl((D, H, N), ("embed", "heads", None)),
            "dt_w": decl((D, H), ("embed", "heads")),
            "dt_bias": decl((H,), ("heads",), init="const", scale=-1.0,
                            dtype=jnp.float32),
            "A_log": decl((H,), ("heads",), init="const", scale=0.5,
                          dtype=jnp.float32),
            "D_skip": decl((H, hd), ("heads", None), init="ones",
                           dtype=jnp.float32),
            "conv_w": decl((CONV_W, Din), (None, "embed"), init="normal"),
            "conv_b": decl((Din,), ("embed",), init="zeros",
                           dtype=jnp.float32),
            "gn_scale": decl((H, hd), ("heads", None), init="ones",
                             dtype=jnp.float32),
            "out_w": decl((H, hd, D), ("heads", None, "embed")),
        },
        "ln2": rmsnorm_decl(D),
        "mlp": swiglu_decl(D, cfg.d_ff),
    }


def param_decls(cfg: ArchConfig):
    return {
        "embed": embed_decl(cfg.vocab, cfg.d_model),
        "layers": stack(_layer_decl(cfg), cfg.n_layers),
        "final_norm": rmsnorm_decl(cfg.d_model),
    }


def cache_decl(cfg: ArchConfig, batch: int, cache_len: int):
    H, hd, N, Din = _dims(cfg)
    L = cfg.n_layers
    d = kvc.kv_cache_decl(L, batch, cache_len, cfg.n_kv_heads, hd)
    d["ssm_S"] = decl((L, batch, H, N, hd),
                      ("layers", "batch", "heads", None, None),
                      init="zeros", dtype=jnp.float32)
    d["conv"] = decl((L, batch, CONV_W - 1, Din),
                     ("layers", "batch", None, "heads"), init="zeros")
    return d


# --------------------------------------------------------------------------

def _causal_conv(u_flat, w, b, conv_state=None):
    """u_flat: (B,S,Din); w: (CONV_W, Din).  Returns (out, new_state)."""
    B, S, Din = u_flat.shape
    if conv_state is None:
        conv_state = jnp.zeros((B, CONV_W - 1, Din), u_flat.dtype)
    ext = jnp.concatenate([conv_state.astype(u_flat.dtype), u_flat], axis=1)
    out = sum(ext[:, j:j + S] * w[j].astype(u_flat.dtype)
              for j in range(CONV_W))
    out = out + b.astype(u_flat.dtype)
    new_state = ext[:, -(CONV_W - 1):]
    return out, new_state


def _ssm_branch(cfg, sp, h, s0=None, conv_state=None, chunk=None):
    """h: (B,S,D) normed input.  Returns (out, new_S, new_conv)."""
    B, S, D = h.shape
    H, hd, N, Din = _dims(cfg)
    u = jnp.einsum("bsd,dhk->bshk", h, sp["in_w"])
    z = jnp.einsum("bsd,dhk->bshk", h, sp["z_w"])
    uc, new_conv = _causal_conv(u.reshape(B, S, Din), sp["conv_w"],
                                sp["conv_b"], conv_state)
    uc = jax.nn.silu(uc.astype(jnp.float32)).astype(h.dtype).reshape(B, S, H, hd)
    Bt = jnp.einsum("bsd,dhn->bshn", h, sp["B_w"])
    Ct = jnp.einsum("bsd,dhn->bshn", h, sp["C_w"])
    dt = jax.nn.softplus(jnp.einsum("bsd,dh->bsh", h, sp["dt_w"])
                         .astype(jnp.float32) + sp["dt_bias"])
    w_log = (-dt * jnp.exp(sp["A_log"]))[..., None]       # (B,S,H,1) <= 0
    k = Bt * dt[..., None].astype(Bt.dtype)               # fold dt into k
    y, s_fin = la.linear_attention(Ct, k, uc, w_log, u=None, s0=s0,
                                   chunk=chunk or cfg.rwkv_chunk)
    y = y + sp["D_skip"].astype(y.dtype) * uc.astype(y.dtype)
    # gated per-head rmsnorm (mamba2-style)
    yf = y.astype(jnp.float32)
    yf = yf * jax.lax.rsqrt(jnp.mean(jnp.square(yf), -1, keepdims=True) + 1e-5)
    yf = yf * sp["gn_scale"]
    y = yf.astype(h.dtype) * jax.nn.silu(z.astype(jnp.float32)).astype(h.dtype)
    out = jnp.einsum("bshk,hkd->bsd", y, sp["out_w"])
    return out, s_fin, new_conv


def _apply_layer(cfg, lp, x, positions):
    h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
    with jax.named_scope("attention"):
        q, k, v = attn.project_qkv(lp["attn"], h, positions, cfg.rope_theta)
        o = attn.attention(q, k, v, positions, positions, causal=True,
                           window=cfg.window, chunk=cfg.attn_chunk,
                           chunk_threshold=cfg.attn_chunk_threshold)
        a_out = attn.project_out(lp["attn"], o)
    with jax.named_scope("ssm"):
        s_out, _, _ = _ssm_branch(cfg, lp["ssm"], h)
    x = x + 0.5 * (a_out + s_out)
    h2 = rmsnorm(lp["ln2"], x, cfg.norm_eps)
    with jax.named_scope("mlp"):
        return x + swiglu(lp["mlp"], h2)


def forward(cfg: ArchConfig, params, batch):
    x = embed_lookup(params["embed"], batch["tokens"])
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)

    def body(x, lp):
        return _apply_layer(cfg, lp, x, positions), None

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = jax.lax.scan(body, x, params["layers"])
    with jax.named_scope("lm_head"):
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return logits_out(params["embed"], x), jnp.float32(0.0)


def prefill(cfg: ArchConfig, params, batch):
    x = embed_lookup(params["embed"], batch["tokens"])
    B, S = x.shape[0], x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    W = min(cfg.window or S, S)

    def body(x, lp):
        h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
        q, k, v = attn.project_qkv(lp["attn"], h, positions, cfg.rope_theta)
        o = attn.attention(q, k, v, positions, positions, causal=True,
                           window=cfg.window, chunk=cfg.attn_chunk,
                           chunk_threshold=cfg.attn_chunk_threshold)
        a_out = attn.project_out(lp["attn"], o)
        s_out, s_fin, conv = _ssm_branch(cfg, lp["ssm"], h)
        x = x + 0.5 * (a_out + s_out)
        h2 = rmsnorm(lp["ln2"], x, cfg.norm_eps)
        x = x + swiglu(lp["mlp"], h2)
        return x, (k[:, -W:], v[:, -W:], s_fin, conv)

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, (kc, vc, S_fin, conv) = jax.lax.scan(body, x, params["layers"])
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = logits_out(params["embed"], x[:, -1])
    kv_pos = jnp.broadcast_to(jnp.arange(S - W, S, dtype=jnp.int32), (B, W))
    return logits, {"k": kc, "v": vc, "kv_pos": kv_pos, "ssm_S": S_fin,
                    "conv": conv}


def decode_step(cfg: ArchConfig, params, cache, batch):
    token, pos = batch["token"], batch["pos"]
    x = embed_lookup(params["embed"], token)
    cache_len = cache["k"].shape[2]
    slot = kvc.cache_slot(pos, cache_len)
    kv_pos = kvc.update_kv_pos(cache["kv_pos"], pos, cache_len)

    def body(x, xs):
        lp, k_l, v_l, S_l, conv_l = xs
        h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
        q, k, v = attn.project_qkv(lp["attn"], h, pos[:, None], cfg.rope_theta)
        k_l, v_l = kvc.update_kv_layer(k_l, v_l, k, v, slot)
        o = attn.decode_attention(q, k_l, v_l, kv_pos, pos, window=cfg.window)
        a_out = attn.project_out(lp["attn"], o)
        s_out, S_n, conv_n = _ssm_branch(cfg, lp["ssm"], h, s0=S_l,
                                         conv_state=conv_l, chunk=1)
        x = x + 0.5 * (a_out + s_out)
        h2 = rmsnorm(lp["ln2"], x, cfg.norm_eps)
        x = x + swiglu(lp["mlp"], h2)
        return x, (k_l, v_l, S_n, conv_n)

    x, (k_new, v_new, S_new, conv_new) = jax.lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"], cache["ssm_S"],
                  cache["conv"]))
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = logits_out(params["embed"], x[:, -1])
    return logits, {"k": k_new, "v": v_new, "kv_pos": kv_pos,
                    "ssm_S": S_new, "conv": conv_new}
