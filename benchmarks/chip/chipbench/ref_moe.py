"""Reference of the repository's ``moe`` decoder family: the dense
decoder's pre-norm grouped-query attention, then, in the leading
``first_k_dense`` layers (the program's ``dense_layers`` stack), a SwiGLU
MLP, and in the rest (``layers``) a mixture of SwiGLU experts::

    gates = softmax(h W_router)                 (float32 router)
    w, e = top_k(gates)                         w / sum(w) over the k picked
    y = sum_j w_j expert_{e_j}(h) + shared(h)   (shared expert if any)

The reference computes every expert on every token and weighs each by its
routing weight (zero where not picked), so it drops no token: it follows
the program where the program's expert capacity holds every token routed
to each expert.  It has no load-balancing loss, so it follows a program
whose configuration states ``moe.aux_coef`` 0, and refuses any other.
Every routing number is read from the configuration's ``model.moe``
(``n_experts``, ``top_k``, ``d_ff_expert``, ``n_shared_experts``,
``first_k_dense``, ``d_ff_dense``), not from the program's registry.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.ref_common import (F32, attention, dense_block_flops, mm,
                                  rmsnorm, swiglu)

STACKS = ("dense_layers", "layers")


def blocks(params) -> list:
    """The leading dense stack, then the expert stack; a stack's kind is
    ``moe`` where its layers hold experts."""
    out = []
    for key in STACKS:
        if key in params:
            kind = "moe" if "moe" in params[key] else "dense"
            depth = params[key]["ln1"]["scale"].shape[0]
            out += [(key, i, kind) for i in range(depth)]
    return out


def experts(Q, p, h, moe: dict):
    if moe.get("aux_coef") != 0:
        raise ValueError("the moe reference has no load-balancing loss: "
                         "the configuration must state moe.aux_coef 0")
    n_exp = p["router"].shape[-1]
    gates = jax.nn.softmax(mm(Q, "bsd,de->bse", h, p["router"]), axis=-1)
    top_w, top_i = jax.lax.top_k(gates, moe["top_k"])
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(top_i, n_exp, dtype=F32)
                     * top_w[..., None], axis=-2)                 # (B,S,E)
    g = mm(Q, "bsd,edf->bsef", h, p["w_gate"])
    u = mm(Q, "bsd,edf->bsef", h, p["w_up"])
    y = mm(Q, "bsef,efd->bsed", jax.nn.silu(g) * u, p["w_down"])
    y = jnp.einsum("bsed,bse->bsd", y, weight)
    if "shared" in p:
        y = y + swiglu(Q, p["shared"], h)
    return y


def layer(Q, lp, x, model, kind):
    eps = model["norm_eps"]
    h = rmsnorm(x, lp["ln1"]["scale"], eps)
    x = x + attention(Q, lp["attn"], h, model["rope_theta"], model["window"])
    h = rmsnorm(x, lp["ln2"]["scale"], eps)
    if kind == "moe":
        return x + experts(Q, lp["moe"], h, model["moe"])
    return x + swiglu(Q, lp["mlp"], h)


def flops_per_token(model, seq: int) -> float:
    """Forward FLOPs per token, embedding lookup excluded: attention in
    every layer; a SwiGLU of width ``d_ff_dense`` in the leading dense
    layers; the router, the ``top_k`` experts a token is routed to and the
    shared experts in the others."""
    d, moe = model["d_model"], model["moe"]
    f = moe["d_ff_expert"]
    attn = dense_block_flops(d, model["n_heads"], model["n_kv_heads"],
                             model["head_dim"], 0, seq, model["window"])
    n_dense = moe["first_k_dense"]
    dense = attn + 3 * 2 * d * moe["d_ff_dense"] if n_dense else 0.0
    routed = (2 * d * moe["n_experts"] + moe["top_k"] * 3 * 2 * d * f
              + moe["n_shared_experts"] * 3 * 2 * d * f)
    return n_dense * dense + (model["n_layers"] - n_dense) * (attn + routed) \
        + 2 * d * model["vocab"]
