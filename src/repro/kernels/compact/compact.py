"""Pallas TPU kernel: blocked exclusive prefix sum of a survivor mask.

The fused pipeline (DESIGN.md §12) front-packs stage survivors *on device*
between the filter trichotomy and refinement — the staged path's
``np.nonzero`` compact-and-reupload is exactly the host sync the chain must
not pay. The scatter destinations of a stable compaction are an exclusive
prefix sum of the mask; this kernel computes it blocked over [BR, 128]
tiles with the running carry held in SMEM across the (sequential on TPU)
grid, so lanes of any length scan in one launch.

Layout: the [N] mask arrives reshaped [R, 128] (int32 0/1, zero-padded);
each grid step scans an [BR, 128] row block in row-major order — in-row
exclusive prefix sum plus row-exclusive block offsets plus the carry — and
bumps the carry by the block's population count. The [1] total output is
revisited by every step; the last step leaves the full count.

Mosaic has no cumsum lowering, so both in-block prefix sums are log-step
(Hillis-Steele) shifted adds: lane rotations by 1, 2, ..., 64 within a row,
then sublane rotations by 1, 2, 4 across the block's row totals, each
masked to the positions the rotation did not wrap into.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["exclusive_scan_pallas"]

#: rows per grid step; with the 128-lane minor dim this is the int32 min tile
BLOCK_ROWS = 8
LANES = 128


def _inclusive_scan(x, axis: int):
    """Log-step inclusive prefix sum of an int32 tile along ``axis``."""
    n = x.shape[axis]
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    step = 1
    while step < n:
        x = x + jnp.where(pos >= step, pltpu.roll(x, step, axis), 0)
        step *= 2
    return x


def _scan_kernel(m_ref, excl_ref, total_ref, carry_ref):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _():
        carry_ref[0] = 0

    m = m_ref[...]                              # [BR, 128] int32 0/1
    inrow = _inclusive_scan(m, 1)               # in-row inclusive sums
    rows = jnp.broadcast_to(inrow[:, LANES - 1:], m.shape)   # row totals
    base = _inclusive_scan(rows, 0) - rows      # row-exclusive offsets
    excl_ref[...] = carry_ref[0] + base + inrow - m
    carry_ref[0] = carry_ref[0] + jnp.sum(m)
    total_ref[0] = carry_ref[0]


def exclusive_scan_pallas(m2d, *, interpret: bool = False):
    """Row-major exclusive prefix sum of an [R, 128] int32 0/1 mask.

    Returns (excl [R, 128] int32, total [1] int32); R must be a multiple of
    ``BLOCK_ROWS``. The grid walks row blocks sequentially, threading the
    running count through an SMEM scratch cell.
    """
    R, L = m2d.shape
    assert L == LANES and R % BLOCK_ROWS == 0, (R, L)
    grid = (R // BLOCK_ROWS,)
    return pl.pallas_call(
        _scan_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((BLOCK_ROWS, LANES), lambda b: (b, 0))],
        out_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda b: (b, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),   # [1] running total
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, LANES), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        interpret=interpret,
    )(m2d)
