"""Reference of the dense decoder layer: pre-norm grouped-query attention
with rotary positions and a sliding window, then a SwiGLU MLP."""
from __future__ import annotations

from chipbench.ref_common import attention, dense_block_flops, rmsnorm, swiglu


def layer(Q, lp, x, model, kind=None):
    eps = model["norm_eps"]
    h = rmsnorm(x, lp["ln1"]["scale"], eps)
    x = x + attention(Q, lp["attn"], h, model["rope_theta"], model["window"])
    return x + swiglu(Q, lp["mlp"], rmsnorm(x, lp["ln2"]["scale"], eps))


def flops_per_token(model, seq: int) -> float:
    """Forward FLOPs per token, embedding lookup excluded."""
    block = dense_block_flops(model["d_model"], model["n_heads"],
                              model["n_kv_heads"], model["head_dim"],
                              model["d_ff"], seq, model["window"])
    return model["n_layers"] * block + 2 * model["d_model"] * model["vocab"]
