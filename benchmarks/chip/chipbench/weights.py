"""Weights made from the seed by the benchmark, for the program and the
reference alike.

The layout (leaf names, shapes, dtypes) is the program's; the values are
the benchmark's own, so the reference takes no weight the program made.
Every client starts from the same weights, as a federation does.  One
jitted call makes the whole tree on the device, in the type it trains in,
with exact arithmetic, so that the weights can be made again bit for bit.

A matrix's spread is one over the square root of its fan-in: the size of
the input axis its product contracts (two axes for ``wo`` and ``out_w``),
counted past every leading axis that stacks layers or experts.  Which axes
stack is the program's own declaration (``stacking`` of its
``param_decls``: leading logical axes named ``layers`` or ``experts``), so
an expert leaf (layers, experts, d, f) contracts d, and every stack of
layers counts, whatever its name.  A layout given by shapes alone (no
declarations) counts one stacking axis on the leaves under ``layers/``.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

# leaves scaled around one, and their spread
_NEAR_ONE = {"scale": 0.1, "gn_scale": 0.1, "D_skip": 0.1}
# contraction over the two axes before the output axis
_FAN_IN_TWO = {"wo", "out_w"}
# logical axes that stack layers or experts ahead of a leaf's own axes
STACK_AXES = ("layers", "experts")
# spread of the odd integers in [-127, 127] that _uniform draws
_INT_STD = 73.9


def leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def _uniform(key, shape, std: float):
    """Uniform values of about ``std`` spread, centred on 0: an odd integer
    of at most 8 bits times a power of two.  Every such value is exact in
    bfloat16, so no compiled program that makes them (or fuses them into
    what uses them) can round them differently."""
    bits = jax.random.bits(key, shape, dtype=jnp.uint32)
    k = 2 * (bits >> 25).astype(jnp.int32) - 127
    step = 2.0 ** round(math.log2(std / _INT_STD))
    return k.astype(jnp.float32) * step


def stacking(decls) -> dict:
    """``{leaf name: leading axes that stack layers or experts}`` of the
    program's parameter declarations (``model_api.param_decls``)."""
    def lead(d):
        n = 0
        while n < len(d.axes) and d.axes[n] in STACK_AXES:
            n += 1
        return n
    flat = jax.tree_util.tree_flatten_with_path(
        decls, is_leaf=lambda x: hasattr(x, "axes"))[0]
    return {leaf_name(p): lead(d) for p, d in flat}


def _leaf(name: str, shape, dtype, key, lead: int):
    last = name.rsplit("/", 1)[-1]
    if last in _NEAR_ONE:
        x = 1.0 + _uniform(key, shape, _NEAR_ONE[last])
    elif last == "dt_bias":
        x = -1.0 + _uniform(key, shape, 0.5)
    elif last == "A_log":
        x = 0.5 + _uniform(key, shape, 0.25)
    elif last == "conv_b":
        x = _uniform(key, shape, 0.05)
    elif last == "conv_w":
        x = _uniform(key, shape, 0.5)
    elif last == "in_table":
        x = _uniform(key, shape, 1.0)
    else:
        dims = shape[lead:]
        if last == "out_table":
            fan_in = dims[-1]
        elif last in _FAN_IN_TWO:
            fan_in = dims[0] * dims[1]
        else:
            fan_in = dims[0]
        x = _uniform(key, shape, 1.0 / math.sqrt(fan_in))
    return x.astype(dtype)


def seed_key(seed: int):
    """A key from any whole number a run is given (wider than 32 bits
    included)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 32)),
                              (seed >> 32) % (1 << 31))


def make(key, like, n_clients: int = 1, stacked: dict | None = None):
    """Values for the parameter tree ``like`` (arrays or shape structs)
    from ``key`` (``seed_key``), with ``stacked`` (``stacking``) giving each
    leaf's leading stacking axes.

    With ``n_clients > 1`` every leaf has a leading clients axis and all
    clients get the same values.  Each leaf's key folds in a hash of its
    name, so a leaf's values do not depend on which other leaves exist."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(like)
    out = []
    for path, leaf in paths:
        name = leaf_name(path)
        shape = leaf.shape[1:] if n_clients > 1 else leaf.shape
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7fffffff)
        lead = (stacked[name] if stacked is not None
                else int(name.startswith("layers/")))
        x = _leaf(name, shape, leaf.dtype, k, lead)
        if n_clients > 1:
            x = jnp.broadcast_to(x[None], leaf.shape)
        out.append(x)
    return jax.tree_util.tree_unflatten(treedef, out)


def make_on_device(seed: int, like, shardings, n_clients: int = 1,
                   stacked: dict | None = None):
    """``make`` as one jitted call, laid out as ``shardings``; the key is
    an argument, so one compiled program serves every seed."""
    return jax.jit(lambda key: make(key, like, n_clients, stacked),
                   out_shardings=shardings)(seed_key(seed))
