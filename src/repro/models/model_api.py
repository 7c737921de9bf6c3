"""Uniform model API over the architecture families + loss functions.

Every family module exposes:
    param_decls(cfg) -> ParamDecl pytree
    forward(cfg, params, batch) -> (logits (B,S,V), aux_loss)
    prefill(cfg, params, batch) -> (last_logits (B,V), cache)
    decode_step(cfg, params, cache, batch) -> (logits (B,V), cache)
    cache_decl(cfg, batch, cache_len) -> ParamDecl pytree
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.dist import sharding as shd
from repro.models import decoder, encdec, hybrid, rwkv6

_FAMILY = {
    "dense": decoder,
    "moe": decoder,
    "vlm": decoder,
    "encdec": encdec,
    "rwkv": rwkv6,
    "hybrid": hybrid,
}


def get_model(cfg: ArchConfig):
    return _FAMILY[cfg.family]


def param_decls(cfg: ArchConfig):
    return get_model(cfg).param_decls(cfg)


def init_params(cfg: ArchConfig, key):
    return shd.materialize(param_decls(cfg), key)


def cache_len_for(cfg: ArchConfig, seq_len: int) -> int:
    if cfg.family == "rwkv":
        return 0  # recurrent state only
    return min(cfg.window, seq_len) if cfg.window else seq_len


# --------------------------------------------------------------------------
# Loss
# --------------------------------------------------------------------------

def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Vocab-parallel-safe CE: never gathers the full vocab to one device.
    The label-logit extraction is an iota-compare+select+reduce, which XLA
    fuses into a streaming pass over the (sharded) vocab dim."""
    lf = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(lf, axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1)) + m[..., 0]
    iota = jax.lax.broadcasted_iota(jnp.int32, lf.shape, len(lf.shape) - 1)
    sel = jnp.where(iota == labels[..., None], lf, 0.0)
    label_logit = jnp.sum(sel, axis=-1)
    return jnp.mean(lse - label_logit)


def loss_fn(cfg: ArchConfig, params, batch):
    logits, aux = get_model(cfg).forward(cfg, params, batch)
    with jax.named_scope("lm_head"):
        ce = cross_entropy(logits, batch["labels"])
    return ce + aux, {"ce": ce, "aux": aux}
