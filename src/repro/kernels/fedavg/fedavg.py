"""Pallas TPU kernel: fused K-way weighted parameter aggregation.

The aggregation hot spot SDFLMQ distributes across cluster heads.  On a
v5e the aggregator reduces K client parameter blocks into one weighted
mean.  The kernel tiles the flattened parameter vector into VMEM-resident
(K, BLOCK) tiles, does the weighted reduction in f32 on the VPU, and
writes one (BLOCK,) tile back — one HBM pass over the inputs, no (K, N)
f32 temporary (the XLA path materializes the f32 upcast).

Grid: (N // BLOCK,).  BLOCK is sized so K * BLOCK * 4B fits comfortably
in VMEM (default 16 MiB/core on v5e): K=16 x 64k x 4B = 4 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK = 65536


def _fedavg_kernel(w_ref, x_ref, o_ref):
    # x_ref: (K, BLOCK) tile in VMEM; w_ref: (K, 1) in SMEM-ish VMEM
    x = x_ref[...].astype(jnp.float32)              # (K, B)
    w = w_ref[...].astype(jnp.float32)              # (K, 1)
    total = jnp.sum(w)
    acc = jnp.sum(x * w, axis=0) / total            # (B,)
    o_ref[...] = acc.astype(o_ref.dtype)


def _qagg_kernel(w_ref, q_ref, s_ref, o_ref):
    # q_ref: (K, RB, G) int8 tile; s_ref: (K, RB, 1) per-row scales;
    # w_ref: (K, 1, 1) client weights.  Dequantize on the VPU and reduce the
    # client axis in f32 — the int8 payload is the only (K, N)-sized HBM
    # traffic; the f32 upcast never leaves VMEM.
    x = q_ref[...].astype(jnp.float32) * s_ref[...]
    acc = jnp.sum(x * w_ref[...], axis=0)               # (RB, G)
    o_ref[...] = acc.astype(o_ref.dtype)


# qagg tiling.  A block's last two dims must be multiples of the chip's
# tile, (32, 128) for int8, or equal the array's own dims.
QAGG_TILE = 65536        # elements of one client's (rows, cols) tile
QAGG_MAX_COLS = 4096     # wider rows are split into 128-lane column blocks
QAGG_MAX_ROWS = 512      # the (K, rows, 1) f32 scale block fills a 128-lane
                         # VMEM tile per row, so rows are capped
_INT8_ROWS, _LANES = 32, 128


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def qagg_tiles(R: int, G: int):
    """Block and padded sizes ``(rows_block, R_pad, cols_block, G_pad)`` for
    an (R, G) grid of int8 rows.  Rows up to ``QAGG_MAX_COLS`` wide are one
    full-width block, legal at any G; wider rows are split evenly into
    column blocks of a multiple of 128 lanes and padded.  Row blocks are
    multiples of the int8 sublane tile, or all R rows when R is smaller."""
    if G <= QAGG_MAX_COLS:
        gb = g_pad = G
    else:
        n_col = -(-G // QAGG_MAX_COLS)
        gb = _round_up(-(-G // n_col), _LANES)
        g_pad = n_col * gb
    rb = (QAGG_TILE // gb) // _INT8_ROWS * _INT8_ROWS
    rb = min(QAGG_MAX_ROWS, max(_INT8_ROWS, rb))
    if R <= rb:
        return R, R, gb, g_pad
    return rb, _round_up(R, rb), gb, g_pad


def qagg_pallas(q: jax.Array, scales: jax.Array, weights: jax.Array,
                interpret: bool = False):
    """Fused dequantize + weighted-sum over clients.

    q: (K, R, G) int8 — R rows of G-wide quantization groups (G is the
    tensor's last dim, matching ``quantize_int8``'s per-row scales);
    scales: (K, R, 1) f32; weights: (K,).  Returns (R, G) f32.  Tiles are
    (K, rows_block, cols_block) from ``qagg_tiles``; every column block of a
    row reads that row's one scale, and zero padding (scale 0) adds nothing.
    """
    K, R, G = q.shape
    rb, r_pad, gb, g_pad = qagg_tiles(R, G)
    if (r_pad, g_pad) != (R, G):
        q = jnp.pad(q, ((0, 0), (0, r_pad - R), (0, g_pad - G)))
        scales = jnp.pad(scales, ((0, 0), (0, r_pad - R), (0, 0)))
    out = pl.pallas_call(
        _qagg_kernel,
        grid=(r_pad // rb, g_pad // gb),
        in_specs=[
            pl.BlockSpec((K, 1, 1), lambda i, j: (0, 0, 0)),
            pl.BlockSpec((K, rb, gb), lambda i, j: (0, i, j)),
            pl.BlockSpec((K, rb, 1), lambda i, j: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((rb, gb), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r_pad, g_pad), jnp.float32),
        interpret=interpret,
    )(weights.reshape(K, 1, 1).astype(jnp.float32), q, scales)
    return out[:R, :G]


def fedavg_pallas(stacked: jax.Array, weights: jax.Array,
                  block: int = DEFAULT_BLOCK, interpret: bool = False):
    """stacked: (K, N) with N % block == 0 (callers pad); weights: (K,)."""
    K, N = stacked.shape
    block = min(block, N)
    assert N % block == 0, (N, block)
    grid = (N // block,)
    return pl.pallas_call(
        _fedavg_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((K, 1), lambda i: (0, 0)),
            pl.BlockSpec((K, block), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((N,), stacked.dtype),
        interpret=interpret,
    )(weights.reshape(K, 1), stacked)
