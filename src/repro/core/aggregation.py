"""Aggregation data plane: pluggable aggregation over an FL-client mesh
axis, executed as compiled collectives inside the FL round step.

The aggregation *strategy* (repro.api.strategies) decides the math; the
*schedule* decides the collective shape.  "sum"-reduction strategies
(fedavg, fedprox) run any schedule:

  * ``tree``       — paper-faithful hierarchical aggregation: one grouped
                     psum per cluster level; non-participants contribute 0.
  * ``flat``       — centralized baseline: one global psum.
  * ``rs_ag``      — beyond-paper: reduce-scatter + all-gather on the
                     largest divisible dim (bandwidth-optimal form).
  * ``compressed`` — beyond-paper: int8 block-quantized all-gather (used on
                     the DCN/pod hop where bandwidth is scarcest) with
                     local weighted combine; introduces bounded error.

"stack"-reduction strategies (trimmed_mean, coordinate_median) are not
decomposable into partial sums, so every schedule lowers to one all-gather
over the client axis followed by a local (replicated) robust combine — the
exact collective analogue of the host path forwarding stacked contributions
up the MQTT tree.  The combine is churn-aware (``combine_masked``): mesh
rows carried with zero FedAvg weight (dead/vacant client slots) are sorted
behind a sentinel and the trim/median window is computed over the *live*
count, so a departed client's stale row cannot shift the robust statistics
— matching the host path's churn-exact behavior with static shapes.

All run under shard_map; the client axis is ``axis`` ("data" in replica
mode, "pod" in shared mode).
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.api.strategies import AggregationStrategy, get_strategy
from repro.core.topology import AggSchedule
from repro.dist.compression import dequantize_int8, quantize_int8


def _weighted(p, w):
    return jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32) * w.astype(jnp.float32), p)


def _tree_psum(contrib, w, axis, schedule: AggSchedule):
    """Hierarchical: grouped psum per level, masking non-heads above L0."""
    total_w = w
    for lvl, groups in enumerate(schedule.level_groups):
        groups_l = [list(g) for g in groups]
        if lvl > 0:
            mask_arr = jnp.asarray(schedule.head_masks[lvl - 1], jnp.float32)
            my = mask_arr[jax.lax.axis_index(axis)]
            contrib = jax.tree_util.tree_map(lambda x: x * my, contrib)
            total_w = total_w * my
        contrib = jax.tree_util.tree_map(
            lambda x: jax.lax.psum(x, axis, axis_index_groups=groups_l), contrib)
        total_w = jax.lax.psum(total_w, axis, axis_index_groups=groups_l)
    return contrib, total_w


def _flat_psum(contrib, w, axis):
    return (jax.tree_util.tree_map(lambda x: jax.lax.psum(x, axis), contrib),
            jax.lax.psum(w, axis))


def _rs_ag(contrib, w, axis, axis_size):
    """reduce_scatter + all_gather on the largest divisible dimension;
    falls back to psum for small/indivisible leaves."""
    def one(x):
        dims = [d for d in range(x.ndim) if x.shape[d] % axis_size == 0
                and x.shape[d] >= axis_size]
        if not dims or x.size < 4 * axis_size:
            return jax.lax.psum(x, axis)
        d = max(dims, key=lambda i: x.shape[i])
        scat = jax.lax.psum_scatter(x, axis, scatter_dimension=d, tiled=True)
        return jax.lax.all_gather(scat, axis, axis=d, tiled=True)
    return (jax.tree_util.tree_map(one, contrib), jax.lax.psum(w, axis))


def _compressed(contrib, w, axis, axis_size):
    """int8-quantized all-gather + fused local combine (DCN hop
    compression).  Only the int8 payload + per-row scales cross the slow
    hop; the dequantize+sum runs as one fused kernel (``qagg`` — Pallas on
    TPU, bit-identical jnp oracle elsewhere) so the gathered (A, ...) f32
    upcast is never materialized in HBM.  Contributions arrive pre-weighted,
    hence weights of 1.0 into the kernel."""
    from repro.kernels.fedavg.ops import qagg

    def one(x):
        q, scale = quantize_int8(x)
        qs = jax.lax.all_gather(q, axis)            # (A, ...) int8
        ss = jax.lax.all_gather(scale, axis)        # (A, ...) f32 scales
        return qagg(qs, ss, jnp.ones((axis_size,), jnp.float32))
    return (jax.tree_util.tree_map(one, contrib), jax.lax.psum(w, axis))


def aggregate_params(params, weights, mesh: Mesh, axis: str,
                     schedule: AggSchedule, param_specs,
                     strategy: Union[str, AggregationStrategy] = "fedavg",
                     ref_params=None):
    """params: client-stacked pytree (leading dim = n_clients, sharded over
    ``axis``); weights: (n_clients,).  Returns the same structure with every
    client's slot holding the identical strategy-aggregated global.

    ``ref_params`` (same structure as ``params``) is the pre-round model for
    strategies with ``needs_ref`` (fedprox): each client's pre-round params
    equal the previous global, so the reference is shard-local — no extra
    collectives."""
    strat = get_strategy(strategy)
    if not strat.compiled:
        raise ValueError(
            f"strategy {strat.name!r} has no compiled collective form "
            "(host path / Federation facade only)")
    axis_size = mesh.shape[axis]
    p_leaves, treedef = jax.tree_util.tree_flatten(params)
    spec_leaves = tuple(treedef.flatten_up_to(param_specs))
    n_p = len(p_leaves)
    ref_leaves = ()
    if strat.needs_ref and ref_params is not None:
        ref_leaves = tuple(jax.tree_util.tree_leaves(ref_params))
        assert len(ref_leaves) == n_p

    def body(w_local, *leaves):
        p_local = jax.tree_util.tree_unflatten(treedef, leaves[:n_p])
        w = w_local.reshape(())                      # this client's weight

        if strat.reduction == "stack":
            # robust combine needs every contribution: one all-gather, then
            # a replicated local combine (identical result on every shard).
            # The combine is churn-aware: rows carried with zero weight
            # (dead/vacant mesh slots) are masked out of the robust
            # statistics instead of feeding them stale parameters.
            # Defense premaps (norm clipping) apply shard-locally BEFORE the
            # gather — client i owns mesh index i, so the local slice is one
            # client's contribution, exactly like a leaf on the host path.
            if ref_leaves:
                ref_local = jax.tree_util.tree_unflatten(treedef, leaves[n_p:])
                p_local = strat.premap(p_local, ref_local, jnp)
            elif type(strat).premap is not AggregationStrategy.premap:
                p_local = strat.premap(p_local, None, jnp)
            stacked = jax.tree_util.tree_map(
                lambda x: jax.lax.all_gather(x, axis, axis=0, tiled=True),
                p_local)
            w_full = jax.lax.all_gather(w_local, axis, axis=0, tiled=True)
            combined = strat.combine_masked(stacked, w_full, jnp)
            out = jax.tree_util.tree_map(
                lambda m, p: m[None].astype(p.dtype), combined, p_local)
            return tuple(jax.tree_util.tree_leaves(out))

        if ref_leaves:
            ref_local = jax.tree_util.tree_unflatten(treedef, leaves[n_p:])
            base = strat.premap(p_local, ref_local, jnp)
        else:
            base = p_local
        contrib = _weighted(base, w)
        if schedule.kind == "tree":
            summed, tw = _tree_psum(contrib, w, axis, schedule)
        elif schedule.kind == "rs_ag":
            summed, tw = _rs_ag(contrib, w, axis, axis_size)
        elif schedule.kind == "compressed":
            summed, tw = _compressed(contrib, w, axis, axis_size)
        else:
            summed, tw = _flat_psum(contrib, w, axis)
        mean = jax.tree_util.tree_map(lambda x: x / tw, summed)
        out = jax.tree_util.tree_map(
            lambda m, p: m.astype(p.dtype), mean, p_local)
        return tuple(jax.tree_util.tree_leaves(out))

    out_leaves = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis),) + spec_leaves + (spec_leaves if ref_leaves else ()),
        out_specs=spec_leaves, check_vma=False,
    )(weights, *(p_leaves + list(ref_leaves)))
    return jax.tree_util.tree_unflatten(treedef, out_leaves)
