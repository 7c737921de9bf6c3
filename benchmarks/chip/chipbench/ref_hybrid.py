"""Reference of the hybrid (Hymba-style) layer as this repository defines
it: attention and a state-space branch side by side on the same normed
input, their outputs averaged, then a SwiGLU MLP.

The state-space branch, for each head, in float32::

    u = silu(causal_conv3(h W_in) + b)         z = h W_z
    dt_t = softplus(h_t w_dt + dt_bias)        a_t = -dt_t exp(A_log)
    S_t = exp(a_t) S_{t-1} + (dt_t B_t) u_t^T   y_t = C_t^T S_t + D u_t
    out = (rmsnorm_head(y) * gn_scale * silu(z)) W_out

computed in its quadratic form: y_t = sum_{s<=t} exp(cum_t - cum_s)
(C_t . dt_s B_s) u_s, with cum the inclusive running sum of a.  Departures
of the repository from the published Hymba (arXiv:2411.13676) that the
reference follows: no meta tokens, no cross-layer key/value sharing, every
layer windowed, and a scalar decay per head (SSD form).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.ref_common import (attention, dense_block_flops, mm, rmsnorm,
                                  swiglu)

CONV_W = 3


def ssm(Q, p, h):
    B, S, D = h.shape
    H, hd = p["in_w"].shape[1], p["in_w"].shape[2]
    u = mm(Q, "bsd,dhk->bshk", h, p["in_w"]).reshape(B, S, H * hd)
    z = mm(Q, "bsd,dhk->bshk", h, p["z_w"])
    pad = jnp.pad(u, ((0, 0), (CONV_W - 1, 0), (0, 0)))
    conv = sum(pad[:, j:j + S] * p["conv_w"][j] for j in range(CONV_W))
    u = jax.nn.silu(conv + p["conv_b"]).reshape(B, S, H, hd)
    Bt = mm(Q, "bsd,dhn->bshn", h, p["B_w"])
    Ct = mm(Q, "bsd,dhn->bshn", h, p["C_w"])
    dt = jax.nn.softplus(mm(Q, "bsd,dh->bsh", h, p["dt_w"]) + p["dt_bias"])
    cum = jnp.cumsum(-dt * jnp.exp(p["A_log"]), axis=1)          # (B,S,H)
    k = Bt * dt[..., None]
    t = jnp.arange(S)
    causal = t[:, None] >= t[None, :]
    gap = cum.transpose(0, 2, 1)[:, :, :, None] - \
        cum.transpose(0, 2, 1)[:, :, None, :]                    # (B,H,t,s)
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, gap, 0.0)), 0.0)
    scores = mm(Q, "bthn,bshn->bhts", Ct, k) * decay
    y = mm(Q, "bhts,bshk->bthk", scores, u) + p["D_skip"] * u
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + 1e-5)
    y = y * p["gn_scale"] * jax.nn.silu(z)
    return mm(Q, "bshk,hkd->bsd", y, p["out_w"])


def layer(Q, lp, x, model, kind=None):
    eps = model["norm_eps"]
    h = rmsnorm(x, lp["ln1"]["scale"], eps)
    a = attention(Q, lp["attn"], h, model["rope_theta"], model["window"])
    x = x + 0.5 * (a + ssm(Q, lp["ssm"], h))
    return x + swiglu(Q, lp["mlp"], rmsnorm(x, lp["ln2"]["scale"], eps))


def flops_per_token(model, seq: int) -> float:
    """Forward FLOPs per token, embedding lookup excluded: attention and
    MLP as a dense block, the state-space projections, and its recurrence
    (state update and read-out, two multiply-adds per state element)."""
    d, H, hd, N = (model["d_model"], model["n_heads"], model["head_dim"],
                   model["ssm_state"])
    din = H * hd
    ssm_proj = 2 * d * (2 * din + 2 * H * N + H) + 2 * din * d
    recurrence = 2 * 2 * H * N * hd + 2 * CONV_W * din
    block = dense_block_flops(d, H, model["n_kv_heads"], hd, model["d_ff"],
                              seq, model["window"])
    return model["n_layers"] * (block + ssm_proj + recurrence) + \
        2 * d * model["vocab"]
