"""Generalized decayed linear attention — the shared computational core of
RWKV6 ("Finch", data-dependent per-channel decay) and the Hymba SSM branch
(SSD-form, scalar per-head decay).

Recurrence (per batch b, head h; d_k = key dim, d_v = value dim):

    S_t = diag(w_t) S_{t-1} + k_t v_t^T            S: (d_k, d_v)
    o_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T)      (u-bonus optional; u=None
                                                    means o_t = r_t^T S_t —
                                                    the SSD convention)

``w_log`` is log-decay, broadcastable to (B, T, H, d_k); a scalar-per-head
decay is passed as (B, T, H, 1).

Two implementations:
  * ``recurrent`` — exact lax.scan over time; the oracle, also used for
    single-token decode.
  * ``chunked``  — scan over chunks; intra-chunk pairwise decay differences
    (all exponents of non-positive numbers -> numerically safe), inter-chunk
    via the carried state.  O(T/C) sequential steps, O(C^2) parallel work —
    this is the TPU-friendly form the Pallas kernel (kernels/wkv6) mirrors.

``chunked`` takes one of two forms of its chunk step, chosen from the
input's shape alone:
  * scalar decay (``w_log.shape[-1] == 1`` and ``u is None``; Hymba): the
    decay factor exp(cum_t - cum_s) is the same for every key channel, so
    the intra-chunk score is (r_t . k_s) exp(cum_t - cum_s) — a matmul times
    one (B, H, C, C) decay matrix per chunk;
  * per channel (a (B, T, H, d_k) decay or a ``u`` bonus; RWKV6): the
    decay differs per key channel, so each chunk builds the pairwise
    (B, C, C, H, d_k) decay tensor and contracts it with r and k.
``form_counts()`` tallies, at trace time, which form each call took.
"""
from __future__ import annotations

from collections import Counter
from typing import Optional

import jax
import jax.numpy as jnp


def _bcast_w(w_log, shape):
    return jnp.broadcast_to(w_log, shape)


# --------------------------------------------------------------------------
# Recurrent (oracle / decode)
# --------------------------------------------------------------------------

def recurrent(r, k, v, w_log, u: Optional[jax.Array] = None, s0=None):
    """r,k: (B,T,H,dk); v: (B,T,H,dv); w_log broadcastable to r.
    Returns (o: (B,T,H,dv), s_final: (B,H,dk,dv))."""
    B, T, H, dk = r.shape
    dv = v.shape[-1]
    w_log = _bcast_w(w_log, r.shape).astype(jnp.float32)
    rf, kf, vf = (a.astype(jnp.float32) for a in (r, k, v))
    if s0 is None:
        s0 = jnp.zeros((B, H, dk, dv), jnp.float32)

    def step(S, inp):
        rt, kt, vt, wt = inp                      # (B,H,dk),(B,H,dk),(B,H,dv),(B,H,dk)
        kv = kt[..., :, None] * vt[..., None, :]  # (B,H,dk,dv)
        if u is not None:
            att = S + u[None, :, :, None] * kv
        else:
            att = jnp.exp(wt)[..., None] * S + kv
        o = jnp.einsum("bhk,bhkv->bhv", rt, att)
        S_new = jnp.exp(wt)[..., None] * S + kv
        return S_new, o

    xs = tuple(a.transpose(1, 0, 2, 3) for a in (rf, kf, vf, w_log))
    s_fin, o = jax.lax.scan(step, s0, xs)
    return o.transpose(1, 0, 2, 3), s_fin


def decode_step(r, k, v, w_log, S, u: Optional[jax.Array] = None):
    """One token.  r,k: (B,H,dk); v: (B,H,dv); w_log (B,H,dk) or (B,H,1);
    S: (B,H,dk,dv).  Returns (o: (B,H,dv), S_new)."""
    rf, kf, vf = (a.astype(jnp.float32) for a in (r, k, v))
    w = jnp.broadcast_to(w_log, rf.shape).astype(jnp.float32)
    kv = kf[..., :, None] * vf[..., None, :]
    if u is not None:
        att = S + u[None, :, :, None] * kv
    else:
        att = jnp.exp(w)[..., None] * S + kv
    o = jnp.einsum("bhk,bhkv->bhv", rf, att)
    S_new = jnp.exp(w)[..., None] * S + kv
    return o.astype(r.dtype), S_new


# --------------------------------------------------------------------------
# Chunked (production path; Pallas kernel mirrors this)
# --------------------------------------------------------------------------

_FORMS: Counter = Counter()


def form_counts() -> dict:
    """How many ``chunked`` calls traced in this process took each form:
    ``{"scalar_decay": n, "per_channel": m}`` (absent keys are 0)."""
    return dict(_FORMS)


def chunked(r, k, v, w_log, u: Optional[jax.Array] = None, s0=None,
            chunk: int = 64):
    """Same contract as ``recurrent``; mathematically identical."""
    B, T, H, dk = r.shape
    dv = v.shape[-1]
    C = min(chunk, T)
    scalar = w_log.shape[-1] == 1 and u is None
    _FORMS["scalar_decay" if scalar else "per_channel"] += 1
    w_log = _bcast_w(w_log, (B, T, H, 1) if scalar else r.shape
                     ).astype(jnp.float32)
    rf, kf, vf = (a.astype(jnp.float32) for a in (r, k, v))
    T_orig = T
    if T % C:
        # pad: k=0 contributes nothing, w_log=0 preserves the state
        pad = C - T % C
        widths = ((0, 0), (0, pad), (0, 0), (0, 0))
        rf, kf, vf = (jnp.pad(a, widths) for a in (rf, kf, vf))
        w_log = jnp.pad(w_log, widths)
        T += pad
    n = T // C

    # keep the recurrence sharded over the model axis: the (B,n,C,H,d)
    # reshape loses GSPMD's seq sharding, which would otherwise replicate
    # the whole scan on every model device (16x HBM traffic).  The
    # recurrence is independent per (batch, head): pin heads when they
    # divide the axis, else batch.
    from repro.models.attention import _active_mesh, _constrain_dim
    mesh = _active_mesh()
    msize = mesh.shape.get("model") if mesh is not None else None

    def _pin(a, h_dim, b_dim):
        if msize is None:
            return a
        if a.shape[h_dim] % msize == 0:
            return _constrain_dim(a, h_dim)
        return _constrain_dim(a, b_dim)

    if scalar:
        if s0 is None:
            s0 = jnp.zeros((B, H, dk, dv), jnp.float32)
        s_fin, o = _scalar_decay_scan(rf, kf, vf, w_log[..., 0],
                                      _pin(s0, 1, 0), C, _pin)
        return o[:, :T_orig], s_fin

    def to_chunks(a, last):
        return a.reshape(B, n, C, H, last).transpose(1, 0, 2, 3, 4)

    rc, kc, wc = (_pin(to_chunks(a, dk), 3, 1) for a in (rf, kf, w_log))
    vc = _pin(to_chunks(vf, dv), 3, 1)
    if s0 is None:
        s0 = jnp.zeros((B, H, dk, dv), jnp.float32)
    s0 = _pin(s0, 1, 0)

    idx = jnp.arange(C)
    lower = idx[:, None] > idx[None, :]            # strictly-causal intra mask

    def chunk_step(S, inp):
        rb, kb, vb, wb = inp                       # (B,C,H,d*)
        cum = jnp.cumsum(wb, axis=1)               # inclusive log-decay
        # RWKV convention (u-bonus) reads S *before* the t-update: exclusive
        # decay; SSD convention (u=None) reads S after: inclusive decay.
        base = (cum - wb) if u is not None else cum
        # ---- inter-chunk: state contribution -------------------------
        q_eff = rb * jnp.exp(base)                 # exp(<=0) safe
        o_inter = jnp.einsum("bchk,bhkv->bchv", q_eff, S)
        # ---- intra-chunk: pairwise decayed scores --------------------
        # diff[t,s,d] = base[t,d] - cum[s,d]  (<= 0 for s < t)
        diff = base[:, :, None] - cum[:, None, :]           # (B,C,C,H,dk)
        diff = jnp.where(lower[None, :, :, None, None], diff, -jnp.inf)
        A = jnp.einsum("bthk,bshk,btshk->bths", rb, kb, jnp.exp(diff))
        if u is not None:
            diag = jnp.einsum("bthk,hk,bthk->bth", rb, u, kb)
        else:
            diag = jnp.einsum("bthk,bthk->bth", rb, kb)
        # A layout is (B, t, H, s): place diag on t == s
        A = A + diag[:, :, :, None] * jnp.eye(C)[None, :, None, :]
        o_intra = jnp.einsum("bths,bshv->bthv", A, vb)
        # ---- state update --------------------------------------------
        cum_last = cum[:, -1]                      # (B,H,dk)
        k_eff = kb * jnp.exp(cum_last[:, None] - cum)
        S_new = S * jnp.exp(cum_last)[..., None] + jnp.einsum(
            "bchk,bchv->bhkv", k_eff, vb)
        return S_new, o_inter + o_intra

    # remat the chunk step: without it the (B,C,C,H,dk) pairwise-decay tensor
    # is saved per chunk for backward (tens of GB); with it only the carried
    # state (B,H,dk,dv) is stacked across steps.
    s_fin, oc = jax.lax.scan(jax.checkpoint(chunk_step, prevent_cse=False),
                             s0, (rc, kc, vc, wc))
    o = oc.transpose(1, 0, 2, 3, 4).reshape(B, T, H, dv)[:, :T_orig]
    return o, s_fin


def _scalar_decay_scan(rf, kf, vf, w, s0, C, pin):
    """The SSD chunk scan for one decay per head: rf, kf (B,T,H,dk), vf
    (B,T,H,dv), w (B,T,H), T a multiple of C.  Chunks are laid out
    (B, H, C, d), so the decay matrix L and the scores A are (B, H, C, C)
    with the chunk's two time axes minor.  Returns (s_final, o (B,T,H,dv))."""
    B, T, H, dk = rf.shape
    dv = vf.shape[-1]
    n = T // C

    def to_chunks(a):                              # (B,T,H,...) -> (n,B,H,C,...)
        a = a.reshape(B, n, C, H, *a.shape[3:])
        return pin(jnp.moveaxis(a, (1, 3), (0, 2)), 2, 1)

    idx = jnp.arange(C)
    causal = idx[:, None] >= idx[None, :]          # inclusive: o_t = r_t^T S_t

    def chunk_step(S, inp):
        rb, kb, vb, wb = inp                       # (B,H,C,d*), wb (B,H,C)
        cum = jnp.cumsum(wb, axis=-1)              # inclusive log-decay
        # ---- inter-chunk: state contribution -------------------------
        o_inter = jnp.einsum("bhtk,bhkv->bhtv", rb * jnp.exp(cum)[..., None],
                             S)
        # ---- intra-chunk: L[t,s] = exp(cum_t - cum_s) for s <= t ----------
        # masked before the exp, so neither value nor gradient meets inf*0
        diff = jnp.where(causal, cum[..., :, None] - cum[..., None, :],
                         -jnp.inf)                 # (B,H,C,C)
        A = jnp.einsum("bhtk,bhsk->bhts", rb, kb) * jnp.exp(diff)
        o_intra = jnp.einsum("bhts,bhsv->bhtv", A, vb)
        # ---- state update --------------------------------------------
        cum_last = cum[..., -1:]                   # (B,H,1)
        k_eff = kb * jnp.exp(cum_last - cum)[..., None]
        S_new = S * jnp.exp(cum_last)[..., None] + jnp.einsum(
            "bhck,bhcv->bhkv", k_eff, vb)
        return S_new, o_inter + o_intra

    # remat the chunk step as the per-channel form does: only the carried
    # state (B,H,dk,dv) is stacked across steps for the backward.
    s_fin, oc = jax.lax.scan(jax.checkpoint(chunk_step, prevent_cse=False),
                             s0, tuple(to_chunks(a) for a in (rf, kf, vf, w)))
    return s_fin, jnp.moveaxis(oc, (0, 2), (1, 3)).reshape(B, T, H, dv)


def linear_attention(r, k, v, w_log, u=None, s0=None, chunk: int = 64,
                     impl: str = "chunked"):
    if impl == "recurrent":
        return recurrent(r, k, v, w_log, u=u, s0=s0)
    return chunked(r, k, v, w_log, u=u, s0=s0, chunk=chunk)
