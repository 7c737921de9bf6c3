"""RWKV6 ("Finch") — attention-free LM with data-dependent per-channel decay.

Time-mix block: token-shift ddlerp (low-rank adapters) -> r/k/v/g/w
projections -> WKV linear-attention recurrence (chunked for training,
recurrent for decode) -> per-head groupnorm, silu(g) gating, out proj.
Channel-mix block: token-shift + squared-relu MLP.

The chunked WKV is `repro.models.linear_attn.chunked`; the Pallas kernel
(kernels/wkv6) implements the same algorithm for TPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.dist.sharding import decl, stack
from repro.models import linear_attn as la
from repro.models.layers import embed_decl, embed_lookup, layernorm, \
    layernorm_decl, logits_out

LORA_R = 64
N_MIX = 6  # base + r,k,v,w,g


def _heads(cfg: ArchConfig):
    hd = cfg.rwkv_head_dim
    assert cfg.d_model % hd == 0
    return cfg.d_model // hd, hd


def _layer_decl(cfg: ArchConfig):
    D = cfg.d_model
    H, hd = _heads(cfg)
    r = min(LORA_R, D)
    return {
        "ln1": layernorm_decl(D),
        "tm": {
            "mu": decl((N_MIX, D), (None, None), init="const", scale=0.5,
                       dtype=jnp.float32),
            "lora_A": decl((5, D, r), (None, "embed", None)),
            "lora_B": decl((5, r, D), (None, None, "embed"), init="zeros"),
            "w0": decl((D,), (None,), init="const", scale=-2.0,
                       dtype=jnp.float32),
            "u": decl((H, hd), ("heads", None), init="normal", scale=8.0,
                      dtype=jnp.float32),
            "wr": decl((D, H, hd), ("embed", "heads", None)),
            "wk": decl((D, H, hd), ("embed", "heads", None)),
            "wv": decl((D, H, hd), ("embed", "heads", None)),
            "wg": decl((D, H, hd), ("embed", "heads", None)),
            "wo": decl((H, hd, D), ("heads", None, "embed")),
            "gn_scale": decl((H, hd), ("heads", None), init="ones",
                             dtype=jnp.float32),
            "gn_bias": decl((H, hd), ("heads", None), init="zeros",
                            dtype=jnp.float32),
        },
        "ln2": layernorm_decl(D),
        "cm": {
            "mu_k": decl((D,), (None,), init="const", scale=0.5,
                         dtype=jnp.float32),
            "mu_r": decl((D,), (None,), init="const", scale=0.5,
                         dtype=jnp.float32),
            "wk": decl((D, cfg.d_ff), ("embed", "mlp")),
            "wv": decl((cfg.d_ff, D), ("mlp", "embed")),
            "wr": decl((D, D), ("embed", "mlp")),
        },
    }


def param_decls(cfg: ArchConfig):
    return {
        "embed": embed_decl(cfg.vocab, cfg.d_model),
        "layers": stack(_layer_decl(cfg), cfg.n_layers),
        "final_norm": layernorm_decl(cfg.d_model),
    }


def cache_decl(cfg: ArchConfig, batch: int, cache_len: int):
    H, hd = _heads(cfg)
    L, D = cfg.n_layers, cfg.d_model
    return {
        "S": decl((L, batch, H, hd, hd), ("layers", "batch", "heads", None, None),
                  init="zeros", dtype=jnp.float32),
        "x_tm": decl((L, batch, D), ("layers", "batch", None), init="zeros"),
        "x_cm": decl((L, batch, D), ("layers", "batch", None), init="zeros"),
    }


# --------------------------------------------------------------------------

def _shift(x, x_prev=None):
    """Token shift: previous token's activation (zeros / carried state)."""
    if x_prev is None:
        prev = jnp.zeros_like(x[:, :1])
    else:
        prev = x_prev[:, None]
    return jnp.concatenate([prev, x[:, :-1]], axis=1)


def _ddlerp(tm, x, xx):
    """Data-dependent lerp -> 5 mixed streams (r,k,v,w,g)."""
    mu = tm["mu"].astype(x.dtype)
    base = x + (xx - x) * mu[0]
    t = jnp.tanh(jnp.einsum("bsd,idr->bsir", base, tm["lora_A"]))
    lora = jnp.einsum("bsir,ird->bsid", t, tm["lora_B"])
    mixed = (x[:, :, None] + (xx - x)[:, :, None]
             * (mu[1:][None, None] + lora))
    return [mixed[:, :, i] for i in range(5)]


def _time_mix(cfg, tm, x, x_prev, s0, chunk):
    """x: (B,S,D).  Returns (out, new_x_prev, new_state)."""
    B, S, D = x.shape
    H, hd = _heads(cfg)
    xx = _shift(x, x_prev)
    mr, mk, mv, mw, mg = _ddlerp(tm, x, xx)
    r = jnp.einsum("bsd,dhk->bshk", mr, tm["wr"])
    k = jnp.einsum("bsd,dhk->bshk", mk, tm["wk"])
    v = jnp.einsum("bsd,dhk->bshk", mv, tm["wv"])
    g = jax.nn.silu(jnp.einsum("bsd,dhk->bshk", mg, tm["wg"])
                    .astype(jnp.float32)).astype(x.dtype)
    # decay: w_log <= 0 always (chunked path relies on this)
    ww = tm["w0"].astype(jnp.float32) + mw.astype(jnp.float32)
    w_log = -jnp.exp(jnp.clip(ww, -12.0, 6.0)).reshape(B, S, H, hd)

    o, s_fin = la.linear_attention(r, k, v, w_log, u=tm["u"], s0=s0,
                                   chunk=chunk)
    # per-head groupnorm
    of = o.astype(jnp.float32)
    mean = jnp.mean(of, axis=-1, keepdims=True)
    var = jnp.var(of, axis=-1, keepdims=True)
    of = (of - mean) * jax.lax.rsqrt(var + 1e-5)
    of = of * tm["gn_scale"] + tm["gn_bias"]
    out = jnp.einsum("bshk,hkd->bsd", of.astype(x.dtype) * g, tm["wo"])
    return out, x[:, -1], s_fin


def _channel_mix(cm, x, x_prev):
    xx = _shift(x, x_prev)
    mk = cm["mu_k"].astype(x.dtype)
    mr = cm["mu_r"].astype(x.dtype)
    xk = x + (xx - x) * mk
    xr = x + (xx - x) * mr
    k = jnp.einsum("bsd,df->bsf", xk, cm["wk"])
    k = jnp.square(jax.nn.relu(k.astype(jnp.float32))).astype(x.dtype)
    kv = jnp.einsum("bsf,fd->bsd", k, cm["wv"])
    rr = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", xr, cm["wr"])
                        .astype(jnp.float32)).astype(x.dtype)
    return rr * kv, x[:, -1]


def _apply_layer(cfg, lp, x, state=None, chunk=None):
    """state: (S, x_tm, x_cm) per layer or None (training from scratch)."""
    s0 = state[0] if state else None
    xp_tm = state[1] if state else None
    xp_cm = state[2] if state else None
    h = layernorm(lp["ln1"], x, cfg.norm_eps)
    tm_out, new_xtm, new_s = _time_mix(cfg, lp["tm"], h, xp_tm, s0,
                                       chunk or cfg.rwkv_chunk)
    x = x + tm_out
    h = layernorm(lp["ln2"], x, cfg.norm_eps)
    cm_out, new_xcm = _channel_mix(lp["cm"], h, xp_cm)
    x = x + cm_out
    return x, (new_s, new_xtm, new_xcm)


def forward(cfg: ArchConfig, params, batch):
    x = embed_lookup(params["embed"], batch["tokens"])

    def body(x, lp):
        x, _ = _apply_layer(cfg, lp, x)
        return x, None

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = jax.lax.scan(body, x, params["layers"])
    with jax.named_scope("lm_head"):
        x = layernorm(params["final_norm"], x, cfg.norm_eps)
        return logits_out(params["embed"], x), jnp.float32(0.0)


def prefill(cfg: ArchConfig, params, batch):
    x = embed_lookup(params["embed"], batch["tokens"])

    def body(x, lp):
        x, st = _apply_layer(cfg, lp, x)
        return x, st

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, (S, x_tm, x_cm) = jax.lax.scan(body, x, params["layers"])
    x = layernorm(params["final_norm"], x, cfg.norm_eps)
    logits = logits_out(params["embed"], x[:, -1])
    return logits, {"S": S, "x_tm": x_tm, "x_cm": x_cm}


def decode_step(cfg: ArchConfig, params, cache, batch):
    x = embed_lookup(params["embed"], batch["token"])  # (B,1,D)

    def body(x, xs):
        lp, S_l, xtm_l, xcm_l = xs
        x, (S_n, xtm_n, xcm_n) = _apply_layer(cfg, lp, x,
                                              state=(S_l, xtm_l, xcm_l),
                                              chunk=1)
        return x, (S_n, xtm_n, xcm_n)

    x, (S, x_tm, x_cm) = jax.lax.scan(
        body, x, (params["layers"], cache["S"], cache["x_tm"], cache["x_cm"]))
    x = layernorm(params["final_norm"], x, cfg.norm_eps)
    logits = logits_out(params["embed"], x[:, -1])
    return logits, {"S": S, "x_tm": x_tm, "x_cm": x_cm}
