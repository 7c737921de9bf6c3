"""What decides ``correct``: the program's first rounds against the plain
reference.

Four numbers are computed; a cell compares those its limits file
(``limits/<workload>.json``) gives a limit, each against that limit:

- ``loss_gap``: the largest gap, over the compared rounds, between the
  program's round loss and the reference's;
- ``grad_gap``: over the leaves, the largest gap between the norm of
  AdamW's first moment after the first round (the gradients as the
  optimizer got them) in the program and in the reference;
- ``grad_gap_median``: the median leaf's gap of the same norms, where the
  largest is set by the rounding noise of one leaf (see PERF.md);
- ``update_gap``: over the leaves, the largest gap between the norms of
  each leaf's change over the compared rounds, read before the next round
  can overwrite the state.

A leaf's gap is measured against the reference's norm of that leaf or of
the median leaf, whichever is larger.  Leaves whose reference first moment
is under a thousandth of the median leaf's are left out of the leaf
numbers: their change is round-off alone.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.weights import leaf_name

NUMBERS = ("loss_gap", "grad_gap", "grad_gap_median", "update_gap")
TINY = 1e-3


def program_sq_norms(tree, n_clients: int) -> dict:
    """Squared norm of each leaf (per client when there are several)."""
    def sq(a):
        a = a.astype(jnp.float32)
        axes = tuple(range(1, a.ndim)) if n_clients > 1 else None
        return jnp.sum(jnp.square(a), axis=axes)
    vals = jax.jit(lambda t: jax.tree_util.tree_map(sq, t))(tree)
    return {leaf_name(p): np.atleast_1d(np.asarray(v, np.float64))
            for p, v in jax.tree_util.tree_flatten_with_path(vals)[0]}


def program_delta_sq(params, params0_fn, key, n_clients: int) -> dict:
    """Squared norm of each leaf's change from the starting weights, which
    ``params0_fn(key)`` makes again inside the same jitted call."""
    def sq(a, b):
        d = a.astype(jnp.float32) - b.astype(jnp.float32)
        axes = tuple(range(1, d.ndim)) if n_clients > 1 else None
        return jnp.sum(jnp.square(d), axis=axes)
    vals = jax.jit(lambda p, k: jax.tree_util.tree_map(
        sq, p, params0_fn(k)))(params, key)
    return {leaf_name(p): np.atleast_1d(np.asarray(v, np.float64))
            for p, v in jax.tree_util.tree_flatten_with_path(vals)[0]}


def _norms(sq: dict, keys) -> np.ndarray:
    """(leaves, clients) norms from squared norms (scalars or per client)."""
    return np.stack([np.sqrt(np.atleast_1d(np.asarray(sq[k], np.float64)))
                     for k in keys])


def kept_leaves(ref_m_sq: dict) -> list[str]:
    keys = sorted(ref_m_sq)
    norms = _norms(ref_m_sq, keys)
    med = np.median(norms, axis=0)
    return [k for k, row in zip(keys, norms) if np.all(row >= TINY * med)]


def leaf_gaps(prog_sq: dict, ref_sq: dict, kept: list[str]) -> np.ndarray:
    """(leaves, clients) gaps of norms; each client's gap is against that
    client's reference norm of the leaf or of its median leaf, whichever
    is larger.  A gap that is not finite reads as infinite."""
    ref = _norms(ref_sq, kept)
    prog = _norms(prog_sq, kept)
    gaps = np.abs(prog - ref) / np.maximum(ref, np.median(ref, axis=0))
    return np.where(np.isfinite(gaps), gaps, np.inf)


def leaf_gap(prog_sq: dict, ref_sq: dict, kept: list[str]):
    """Worst gap of norms over leaves and clients, and the leaf it is at."""
    gaps = leaf_gaps(prog_sq, ref_sq, kept).max(axis=1)
    i = int(np.argmax(gaps))
    return float(gaps[i]), kept[i]


def median_leaf_gap(prog_sq: dict, ref_sq: dict, kept: list[str]) -> float:
    """The median leaf's gap (the worst client's)."""
    return float(np.max(np.median(leaf_gaps(prog_sq, ref_sq, kept), axis=0)))


def compare(prog: dict, ref: dict) -> dict:
    """The compared numbers, from the program's readings and the
    reference's (``loss``, ``m_sq``, ``delta_sq`` each)."""
    kept = kept_leaves(ref["m_sq"])
    loss_gap = float("inf")
    if len(prog["loss"]) == len(ref["loss"]):
        loss_gap = max(abs(a - b) for a, b in zip(prog["loss"], ref["loss"]))
        if not np.isfinite(loss_gap):
            loss_gap = float("inf")
    grad_gap, grad_at = leaf_gap(prog["m_sq"], ref["m_sq"], kept)
    grad_median = median_leaf_gap(prog["m_sq"], ref["m_sq"], kept)
    update_gap, update_at = leaf_gap(prog["delta_sq"], ref["delta_sq"], kept)
    norms = {k: [_norms(prog["m_sq"], [k])[0].tolist(),
                 _norms(ref["m_sq"], [k])[0].tolist(),
                 _norms(prog["delta_sq"], [k])[0].tolist(),
                 _norms(ref["delta_sq"], [k])[0].tolist()]
             for k in ref["m_sq"]}
    return {"loss_gap": float(loss_gap), "grad_gap": grad_gap,
            "grad_gap_median": grad_median, "update_gap": update_gap, "grad_at": grad_at,
            "update_at": update_at,
            "left_out": sorted(set(ref["m_sq"]) - set(kept)),
            "norms": norms}


def judge(numbers: dict, limits: dict):
    """``(correct, lines)``: each number the cell compares beside its
    limit."""
    ok, lines = True, {}
    compared = [name for name in NUMBERS if name in limits]
    if not compared:
        raise ValueError("the limits file compares no number")
    for name in compared:
        val, lim = numbers[name], limits[name]
        good = bool(np.isfinite(val) and val <= lim)
        ok &= good
        lines[name] = {"value": val, "limit": lim}
    return ok, lines
