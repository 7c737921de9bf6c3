"""jit'd wrapper: platform dispatch + shape plumbing for the fedavg kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.fedavg.fedavg import (DEFAULT_BLOCK, fedavg_pallas,
                                         qagg_pallas)
from repro.kernels.fedavg.ref import fedavg_ref, qagg_ref


def _pad_flat(x_flat: jax.Array, block: int):
    n = x_flat.shape[-1]
    pad = (-n) % block
    if pad:
        x_flat = jnp.pad(x_flat, ((0, 0), (0, pad)))
    return x_flat, n


@functools.partial(jax.jit, static_argnames=("block", "force"))
def fedavg(stacked: jax.Array, weights: jax.Array,
           block: int = DEFAULT_BLOCK, force: str = "auto") -> jax.Array:
    """Weighted mean over the leading (clients) axis of (K, N).

    force: "pallas" (interpret on CPU), "ref", or "auto" (pallas on TPU,
    ref elsewhere — the dry-run must lower without a TPU backend)."""
    K, N = stacked.shape
    use = force
    if use == "auto":
        use = "pallas" if jax.default_backend() == "tpu" else "ref"
    if use == "ref":
        return fedavg_ref(stacked, weights)
    interpret = jax.default_backend() != "tpu"
    padded, n = _pad_flat(stacked, min(block, max(N, 1)))
    out = fedavg_pallas(padded, weights, block=min(block, padded.shape[1]),
                        interpret=interpret)
    return out[:n]


@functools.partial(jax.jit, static_argnames=("force",))
def qagg(q: jax.Array, scales: jax.Array, weights: jax.Array,
         force: str = "auto") -> jax.Array:
    """Fused int8 dequantize + weighted sum over the leading client axis.

    q: (K, *shape) int8 with ``quantize_int8``-style per-last-dim-row scales
    (K, *shape[:-1], 1).  Returns the f32 weighted sum shaped ``shape``.
    force: "pallas" (interpret on CPU), "ref", or "auto"."""
    K = q.shape[0]
    shape = q.shape[1:]
    G = shape[-1] if shape else 1
    q3 = q.reshape(K, -1, G)
    s3 = scales.reshape(K, -1, 1)
    use = force
    if use == "auto":
        use = "pallas" if jax.default_backend() == "tpu" else "ref"
    if use == "ref":
        return qagg_ref(q3, s3, weights).reshape(shape)
    interpret = jax.default_backend() != "tpu"
    return qagg_pallas(q3, s3, weights, interpret=interpret).reshape(shape)


def fedavg_pytree(params_stacked, weights, force: str = "auto"):
    """Apply fedavg leaf-wise over a client-stacked parameter pytree."""
    def one(leaf):
        K = leaf.shape[0]
        flat = leaf.reshape(K, -1)
        return fedavg(flat, weights, force=force).reshape(leaf.shape[1:])
    return jax.tree_util.tree_map(one, params_stacked)
