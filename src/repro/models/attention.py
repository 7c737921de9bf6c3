"""Attention: GQA / MHA, sliding-window, chunked (memory-efficient) and
single-token decode variants.  All math in the XLA-native path so the
multi-pod dry-run lowers on any backend; the Pallas flash kernel is used via
``kernels/flash_attn/ops.py`` when running on a real TPU.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.dist.sharding import decl
from repro.models.layers import rope

NEG_INF = -1e30


# --------------------------------------------------------------------------
# Declarations
# --------------------------------------------------------------------------

def attention_decl(d_model: int, n_heads: int, n_kv: int, head_dim: int,
                   qkv_bias: bool = False):
    d = {
        "wq": decl((d_model, n_heads, head_dim), ("embed", "heads", None)),
        "wk": decl((d_model, n_kv, head_dim), ("embed", "kv_heads", None)),
        "wv": decl((d_model, n_kv, head_dim), ("embed", "kv_heads", None)),
        "wo": decl((n_heads, head_dim, d_model), ("heads", None, "embed")),
    }
    if qkv_bias:
        d["bq"] = decl((n_heads, head_dim), ("heads", None), init="zeros", dtype=jnp.float32)
        d["bk"] = decl((n_kv, head_dim), ("kv_heads", None), init="zeros", dtype=jnp.float32)
        d["bv"] = decl((n_kv, head_dim), ("kv_heads", None), init="zeros", dtype=jnp.float32)
    return d


def project_qkv(params, x, positions, theta: float, *, apply_rope: bool = True):
    """x: (B, S, D) -> q (B,S,H,hd), k/v (B,S,K,hd)."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if "bq" in params:
        q = q + params["bq"].astype(q.dtype)
        k = k + params["bk"].astype(k.dtype)
        v = v + params["bv"].astype(v.dtype)
    if apply_rope:
        q = rope(q, positions, theta)
        k = rope(k, positions, theta)
    return q, k, v


def project_out(params, o):
    """o: (B, S, H, hd) -> (B, S, D)."""
    return jnp.einsum("bshk,hkd->bsd", o, params["wo"])


# --------------------------------------------------------------------------
# Masking helpers
# --------------------------------------------------------------------------

def _mask_bias(q_pos, kv_pos, causal: bool, window: Optional[int]):
    """(…, Sq, Sk) additive bias from position constraints."""
    ok = jnp.ones(q_pos.shape[-1:] + kv_pos.shape[-1:], dtype=bool)
    if causal:
        ok &= q_pos[:, None] >= kv_pos[None, :]
    if window is not None:
        ok &= q_pos[:, None] - kv_pos[None, :] < window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


# --------------------------------------------------------------------------
# Full (quadratic) attention — short sequences
# --------------------------------------------------------------------------

def full_attention(q, k, v, q_pos, kv_pos, *, causal: bool,
                   window: Optional[int] = None) -> jax.Array:
    """q: (B,Sq,H,hd); k,v: (B,Sk,K,hd) with H % K == 0."""
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, Sq, K, G, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    scores = scores + _mask_bias(q_pos, kv_pos, causal, window)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgqs,bskh->bqkgh", probs, v)
    return o.reshape(B, Sq, H, hd)


# --------------------------------------------------------------------------
# Flash attention (pure JAX): two-level chunking + custom_vjp that
# recomputes scores in the backward pass.  Without this, scan residuals
# (per-chunk score tensors) dominate device memory.  The Pallas kernel
# (kernels/flash_attn) mirrors this algorithm; this is also its oracle's
# memory-efficient production form.
# --------------------------------------------------------------------------

def _pad_to(x, n, axis):
    pad = n - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _active_mesh():
    from jax._src.mesh import thread_resources
    m = thread_resources.env.physical_mesh
    return None if m.empty else m


def _constrain_dim(x, dim, axis_name="model"):
    """Pin one dim to a mesh axis (UNCONSTRAINED elsewhere) when a mesh is
    active and sizes divide; no-op otherwise.  This is what keeps the
    q-chunk dim of flash attention sharded through the kv scan — GSPMD
    propagation alone replicates it."""
    m = _active_mesh()
    if m is None or axis_name not in m.axis_names:
        return x
    if x.shape[dim] % m.shape[axis_name] != 0 or x.shape[dim] == 0:
        return x
    from jax.sharding import NamedSharding, PartitionSpec
    spec = [PartitionSpec.UNCONSTRAINED] * x.ndim
    spec[dim] = axis_name
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(m, PartitionSpec(*spec)))


def _nq_for(Sq, chunk_q):
    """Number of q chunks: prefer the model-axis size so the chunk dim
    shards exactly; fall back to ceil(S/chunk)."""
    m = _active_mesh()
    if m is not None and "model" in m.axis_names:
        ma = m.shape["model"]
        if Sq % ma == 0 and Sq // ma >= 1:
            return ma
    return max(1, -(-Sq // chunk_q))


def _mask_bias_chunks(q_pos_c, kv_pos_c, causal, window):
    """q_pos_c: (nq,Cq); kv_pos_c: (Ck,) -> bias (nq,Cq,Ck)."""
    ok = jnp.ones(q_pos_c.shape + kv_pos_c.shape[-1:], dtype=bool)
    if causal:
        ok &= q_pos_c[..., None] >= kv_pos_c[None, None, :]
    if window is not None:
        ok &= q_pos_c[..., None] - kv_pos_c[None, None, :] < window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _chunk_inputs(q, k, v, chunk_q, chunk_k, q_offset, kv_offset):
    B, Sq, H, hd = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    nq = _nq_for(Sq, chunk_q)
    Cq = -(-Sq // nq)
    Ck = min(chunk_k, Sk)
    nk = -(-Sk // Ck)
    q_pos = q_offset + jnp.arange(nq * Cq, dtype=jnp.int32)
    kv_pos = jnp.where(jnp.arange(nk * Ck) < Sk,
                       kv_offset + jnp.arange(nk * Ck, dtype=jnp.int32), 2**30)
    qc = _pad_to(q.reshape(B, Sq, K, G, hd), nq * Cq, 1)         .reshape(B, nq, Cq, K, G, hd)
    qc = _constrain_dim(qc, 1)
    kcs = _pad_to(k, nk * Ck, 1).reshape(B, nk, Ck, K, hd).transpose(1, 0, 2, 3, 4)
    vcs = _pad_to(v, nk * Ck, 1).reshape(B, nk, Ck, K, hd).transpose(1, 0, 2, 3, 4)
    pcs = kv_pos.reshape(nk, Ck)
    qpos_c = q_pos.reshape(nq, Cq)
    return qc, kcs, vcs, pcs, qpos_c, (B, Sq, Sk, H, K, G, hd, nq, Cq, nk, Ck)


def _flash_impl(q, k, v, causal, window, chunk_q, chunk_k,
                q_offset, kv_offset):
    qc, kcs, vcs, pcs, qpos_c, dims = _chunk_inputs(
        q, k, v, chunk_q, chunk_k, q_offset, kv_offset)
    B, Sq, Sk, H, K, G, hd, nq, Cq, nk, Ck = dims
    scale = 1.0 / math.sqrt(hd)

    def step(carry, inp):
        m, l, acc = carry
        kb, vb, pb = inp
        s = jnp.einsum("bnckgh,bskh->bnkgcs", qc, kb,
                       preferred_element_type=jnp.float32) * scale
        s = s + _mask_bias_chunks(qpos_c, pb, causal, window)[:, None, None]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bnkgcs,bskh->bnkgch", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = _constrain_dim(jnp.full((B, nq, K, G, Cq), NEG_INF, jnp.float32), 1)
    l0 = _constrain_dim(jnp.zeros((B, nq, K, G, Cq), jnp.float32), 1)
    a0 = _constrain_dim(jnp.zeros((B, nq, K, G, Cq, hd), jnp.float32), 1)
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(step, prevent_cse=False),
                                  (m0, l0, a0), (kcs, vcs, pcs))
    lse = m + jnp.log(jnp.maximum(l, 1e-30))          # (B,nq,K,G,Cq)
    o = acc / jnp.maximum(l, 1e-30)[..., None]        # (B,nq,K,G,Cq,hd)
    o = o.transpose(0, 1, 4, 2, 3, 5).reshape(B, nq * Cq, H, hd)[:, :Sq]
    return o.astype(q.dtype), lse


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal=True, window=None, chunk_q=512,
                    chunk_k=1024, q_offset=0, kv_offset=0):
    o, _ = _flash_impl(q, k, v, causal, window, chunk_q, chunk_k,
                       q_offset, kv_offset)
    return o


def _flash_fwd(q, k, v, causal, window, chunk_q, chunk_k, q_offset, kv_offset):
    o, lse = _flash_impl(q, k, v, causal, window, chunk_q, chunk_k,
                         q_offset, kv_offset)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, window, chunk_q, chunk_k, q_offset, kv_offset,
               res, do):
    q, k, v, o, lse = res
    qc, kcs, vcs, pcs, qpos_c, dims = _chunk_inputs(
        q, k, v, chunk_q, chunk_k, q_offset, kv_offset)
    B, Sq, Sk, H, K, G, hd, nq, Cq, nk, Ck = dims
    scale = 1.0 / math.sqrt(hd)

    D = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    doc = _constrain_dim(_pad_to(do.reshape(B, Sq, K, G, hd), nq * Cq, 1)
                         .reshape(B, nq, Cq, K, G, hd), 1)
    Dc = _constrain_dim(_pad_to(D.reshape(B, Sq, K, G), nq * Cq, 1)
                        .reshape(B, nq, Cq, K, G), 1)
    lse_e = lse[..., None]                             # (B,nq,K,G,Cq,1)

    def step(dq, inp):
        kb, vb, pb = inp
        s = jnp.einsum("bnckgh,bskh->bnkgcs", qc, kb,
                       preferred_element_type=jnp.float32) * scale
        s = s + _mask_bias_chunks(qpos_c, pb, causal, window)[:, None, None]
        p = jnp.exp(s - lse[..., None])
        dv_c = jnp.einsum("bnkgcs,bnckgh->bskh", p,
                          doc.astype(jnp.float32))
        dp = jnp.einsum("bnckgh,bskh->bnkgcs", doc, vb,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - Dc.transpose(0, 1, 3, 4, 2)[..., None]) * scale
        dq = dq + jnp.einsum("bnkgcs,bskh->bnckgh", ds, kb)
        dk_c = jnp.einsum("bnkgcs,bnckgh->bskh", ds, qc.astype(jnp.float32))
        return dq, (dk_c, dv_c)

    dq0 = _constrain_dim(jnp.zeros((B, nq, Cq, K, G, hd), jnp.float32), 1)
    dq, (dk_s, dv_s) = jax.lax.scan(jax.checkpoint(step, prevent_cse=False),
                                    dq0, (kcs, vcs, pcs))
    dq = dq.reshape(B, nq * Cq, H, hd)[:, :Sq]
    dk = dk_s.transpose(1, 0, 2, 3, 4).reshape(B, nk * Ck, K, hd)[:, :Sk]
    dv = dv_s.transpose(1, 0, 2, 3, 4).reshape(B, nk * Ck, K, hd)[:, :Sk]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def attention(q, k, v, q_pos, kv_pos, *, causal: bool,
              window: Optional[int] = None, chunk: int = 1024,
              chunk_threshold: int = 1024) -> jax.Array:
    """Dispatch: exact quadratic for short kv, flash for long.  q_pos/kv_pos
    must be contiguous ranges for the flash path (always true for our
    training/prefill calls); decode uses decode_attention instead."""
    if k.shape[1] <= chunk_threshold:
        return full_attention(q, k, v, q_pos, kv_pos, causal=causal, window=window)
    return flash_attention(q, k, v, causal, window, min(chunk // 2, 512),
                           chunk)


# --------------------------------------------------------------------------
# Single-token decode attention
# --------------------------------------------------------------------------

def decode_attention(q, k_cache, v_cache, kv_pos, pos, *,
                     window: Optional[int] = None) -> jax.Array:
    """q: (B,1,H,hd); caches: (B,S,K,hd); kv_pos: (B,S) absolute positions
    stored in each cache slot (-1 = empty); pos: (B,) current position."""
    B, _, H, hd = q.shape
    K = k_cache.shape[2]
    G = H // K
    qg = q.reshape(B, K, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg, k_cache,
                   preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(jnp.float32(hd))
    ok = (kv_pos >= 0) & (kv_pos <= pos[:, None])
    if window is not None:
        ok &= pos[:, None] - kv_pos < window
    s = jnp.where(ok[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgs,bskh->bkgh", p, v_cache)
    return o.reshape(B, 1, H, hd)
