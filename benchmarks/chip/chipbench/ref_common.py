"""Plain float32 building blocks of the reference models.

Written from the layer equations, in ``jax.numpy``, with no kernel, chunk,
cache or batching trick of the program; matrix products at
``jax.default_matmul_precision("highest")`` (set by the caller).  ``Q``
stands for the precision of every matrix product: exact float32 for the
reference (``EXACT``), or float8 for the control (``Float8``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _round(x, dtype):
    """``x`` rounded to ``dtype`` with one scale per tensor (its largest
    magnitude onto the type's largest value), back in float32."""
    s = jnp.max(jnp.abs(x)) / float(jnp.finfo(dtype).max)
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(dtype).astype(F32) * s


class Exact:
    """Float32 matrix products."""

    @staticmethod
    def operand(x):
        return x

    @staticmethod
    def output(y):
        return y


class Float8:
    """Float8 training arithmetic: the forward operands of every matrix
    product rounded to e4m3 and the gradient arriving at its output to
    e5m2, each with one scale per tensor; products accumulate in float32."""

    @staticmethod
    def operand(x):
        return x + jax.lax.stop_gradient(_round(x, jnp.float8_e4m3fn) - x)

    @staticmethod
    @jax.custom_vjp
    def output(y):
        return y


Float8.output.defvjp(lambda y: (y, None),
                     lambda _, ct: (_round(ct, jnp.float8_e5m2),))

EXACT = Exact()


def mm(Q, spec, a, b):
    return Q.output(jnp.einsum(spec, Q.operand(a), Q.operand(b)))


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """Rotary embedding on (B, S, heads, hd), positions 0..S-1: the first
    and second halves of each head rotate as pairs."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * freq
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., 2 * half:]], -1)


def attention(Q, p, h, theta, window):
    """Grouped-query causal softmax attention with a sliding window;
    query head ``k * G + g`` reads key/value head ``k``.  One key/value
    head group at a time, recomputed in the backward pass, so the score
    matrices of all heads are never held at once."""
    q = rope(mm(Q, "bsd,dhk->bshk", h, p["wq"]), theta)
    k = rope(mm(Q, "bsd,dhk->bshk", h, p["wk"]), theta)
    v = mm(Q, "bsd,dhk->bshk", h, p["wv"])
    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    t = jnp.arange(S)
    ok = t[:, None] >= t[None, :]
    if window is not None:
        ok &= t[:, None] - t[None, :] < window

    @jax.checkpoint
    def group(args):
        qg, kg, vg = args                      # (B,S,G,hd), (B,S,hd) x2
        s = mm(Q, "bqgh,bsh->bgqs", qg, kg) / jnp.sqrt(F32(hd))
        s = jnp.where(ok, s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        return mm(Q, "bgqs,bsh->bqgh", pr, vg)

    qg = q.reshape(B, S, K, G, hd).transpose(2, 0, 1, 3, 4)
    o = jax.lax.map(group, (qg, k.transpose(2, 0, 1, 3),
                            v.transpose(2, 0, 1, 3)))
    o = o.transpose(1, 2, 0, 3, 4).reshape(B, S, H, hd)
    return mm(Q, "bshk,hkd->bsd", o, p["wo"])


def swiglu(Q, p, x):
    g = mm(Q, "bsd,df->bsf", x, p["w_gate"])
    u = mm(Q, "bsd,df->bsf", x, p["w_up"])
    return mm(Q, "bsf,fd->bsd", jax.nn.silu(g) * u, p["w_down"])


def blocks(params) -> list:
    """The forward pass of a family with one uniform stack: every layer of
    ``layers`` in order (its depth read from ``ln1``), of no kind."""
    return [("layers", i, None)
            for i in range(params["layers"]["ln1"]["scale"].shape[0])]


def head_loss(Q, top, x, labels, model):
    """Sum over tokens of the cross-entropy of the RMSNorm head over the
    untied output table ``embed/out_table``, over every row of the table."""
    logits = mm(Q, "bsd,vd->bsv",
                rmsnorm(x, top["final_norm"]["scale"], model["norm_eps"]),
                top["embed"]["out_table"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum(lse - picked)


def causal_ctx(seq: int, window) -> float:
    """Mean number of keys a query attends to under a causal window."""
    w = seq if window is None else min(window, seq)
    # positions t < w see t + 1 keys, the rest see w
    return (w * (w + 1) / 2 + (seq - w) * w) / seq


def dense_block_flops(d, heads, kv, hd, d_ff, seq, window) -> float:
    """Forward FLOPs per token of attention (projections, scores and
    values under the causal window) and a SwiGLU MLP."""
    proj = 2 * d * (heads + 2 * kv) * hd + 2 * heads * hd * d
    scores = 2 * 2 * heads * hd * causal_ctx(seq, window)
    return proj + scores + 3 * 2 * d * d_ff
