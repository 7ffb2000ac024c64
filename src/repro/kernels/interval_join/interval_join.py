"""Pallas TPU kernel: batched sorted-interval-list overlap join.

The paper's intermediate filter reduces to "do two sorted disjoint interval
lists share a point?" per candidate pair (AA/AF/FA joins). On CPU this is a
branchy two-pointer merge; on TPU we evaluate the overlap predicate for all
(i, j) interval pairs of a tile at once on the VPU — lists are short (tens of
intervals), so the O(I*J) lane-parallel pass beats any serial walk and needs
no gather/scatter.

Tiling: grid (B/BB, J/JB); each program holds BB pair-rows of X intervals
([BB, I]) and a JB-wide slab of Y intervals in VMEM, materializes the
[BB, I, JB] predicate, reduces over (I, JB), and ORs into the [BB, 1]
output column. Endpoints are biased-int32, inclusive-last (see
core/april.py); X rows are masked by their true interval counts, Y slabs by
theirs.

TPU layout: per-row scalars (counts in, verdicts out) travel as [B, 1]
int32 columns — a rank-1 block of BB < 128 rows breaks the lane tiling, and
Mosaic cannot relayout i1 vectors into the 3-D predicate, so the validity
masks are built as int32 before the broadcast.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["interval_overlap_pallas", "april_trichotomy_pallas"]

TRUE_NEG, TRUE_HIT, INDECISIVE = 0, 1, 2   # mirrors core.join


def _any_overlap(xs, xl, nx, ys, yl, ny, j0=0):
    """[BB, 1] int32 0/1: lane-parallel overlap reduction of one pair of
    list slabs (the [BB, I, J] predicate materialized in VMEM, masked by
    the true counts ``nx``/``ny`` [BB, 1]; ``j0`` offsets the Y slab)."""
    BB, I = xs.shape
    J = ys.shape[1]
    xv = (jax.lax.broadcasted_iota(jnp.int32, (BB, I), 1) < nx
          ).astype(jnp.int32)
    yv = (jax.lax.broadcasted_iota(jnp.int32, (BB, J), 1) + j0 < ny
          ).astype(jnp.int32)
    ovl = ((ys[:, None, :] <= xl[:, :, None])
           & (xs[:, :, None] <= yl[:, None, :])
           & (xv[:, :, None] * yv[:, None, :] > 0))
    return jnp.max(jnp.max(ovl.astype(jnp.int32), axis=2), axis=1,
                   keepdims=True)


def _kernel(nx_ref, ny_ref, xs_ref, xl_ref, ys_ref, yl_ref, out_ref, *, jb_size):
    jb = pl.program_id(1)
    any_hit = _any_overlap(xs_ref[...], xl_ref[...], nx_ref[...],
                           ys_ref[...], yl_ref[...], ny_ref[...],
                           j0=jb * jb_size)

    @pl.when(jb == 0)
    def _():
        out_ref[...] = any_hit

    @pl.when(jb != 0)
    def _():
        out_ref[...] = jnp.maximum(out_ref[...], any_hit)


def interval_overlap_pallas(
    xs, xl, nx, ys, yl, ny, *, block_b: int = 8, block_j: int = 128,
    interpret: bool = False,
):
    """[B, 1] int32 (1 = overlap): does pair b's X list overlap its Y list?

    xs/xl: [B, I] int32 (biased, inclusive-last, padded with INT32_MAX);
    ys/yl: [B, J]; nx/ny: [B, 1] int32 true counts.
    """
    B, I = xs.shape
    J = ys.shape[1]
    assert B % block_b == 0 and J % block_j == 0, (B, J, block_b, block_j)
    grid = (B // block_b, J // block_j)
    col = pl.BlockSpec((block_b, 1), lambda b, j: (b, 0))

    return pl.pallas_call(
        partial(_kernel, jb_size=block_j),
        grid=grid,
        in_specs=[
            col,                                                    # nx
            col,                                                    # ny
            pl.BlockSpec((block_b, I), lambda b, j: (b, 0)),        # xs
            pl.BlockSpec((block_b, I), lambda b, j: (b, 0)),        # xl
            pl.BlockSpec((block_b, block_j), lambda b, j: (b, j)),  # ys
            pl.BlockSpec((block_b, block_j), lambda b, j: (b, j)),  # yl
        ],
        out_specs=col,
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.int32),
        interpret=interpret,
    )(nx, ny, xs, xl, ys, yl)


def _trichotomy_kernel(nra_ref, nrf_ref, nsa_ref, nsf_ref,
                       ras_ref, ral_ref, rfs_ref, rfl_ref,
                       sas_ref, sal_ref, sfs_ref, sfl_ref, out_ref):
    """Fused APRIL trichotomy (Algorithm 2): AA + AF + FA joins and the
    verdict select in ONE pass over the block — a bucketed batch needs a
    single kernel launch instead of three overlap launches."""
    nra = nra_ref[...]; nrf = nrf_ref[...]
    nsa = nsa_ref[...]; nsf = nsf_ref[...]
    aa = _any_overlap(ras_ref[...], ral_ref[...], nra,
                      sas_ref[...], sal_ref[...], nsa)
    af = _any_overlap(ras_ref[...], ral_ref[...], nra,
                      sfs_ref[...], sfl_ref[...], nsf)
    fa = _any_overlap(rfs_ref[...], rfl_ref[...], nrf,
                      sas_ref[...], sal_ref[...], nsa)
    out_ref[...] = jnp.where(
        aa == 0, TRUE_NEG,
        jnp.where((af | fa) != 0, TRUE_HIT, INDECISIVE)).astype(jnp.int32)


def april_trichotomy_pallas(
    nra, nrf, nsa, nsf, ras, ral, rfs, rfl, sas, sal, sfs, sfl, *,
    block_b: int = 8, interpret: bool = False,
):
    """[B, 1] int32 verdicts (TRUE_NEG / TRUE_HIT / INDECISIVE) per pair row.

    ras/ral: [B, Ia] A(r); rfs/rfl: [B, If] F(r); sas/sal: [B, Ja] A(s);
    sfs/sfl: [B, Jf] F(s) — biased int32, inclusive-last, INT32_MAX padded;
    n*: [B, 1] int32 true counts. Width bounding is the caller's bucketing
    job (core.join buckets by power-of-two list width, DESIGN.md §9).
    """
    B, Ia = ras.shape
    If = rfs.shape[1]
    Ja = sas.shape[1]
    Jf = sfs.shape[1]
    assert B % block_b == 0, (B, block_b)
    grid = (B // block_b,)

    def mat(w):
        return pl.BlockSpec((block_b, w), lambda b: (b, 0))

    col = pl.BlockSpec((block_b, 1), lambda b: (b, 0))
    return pl.pallas_call(
        _trichotomy_kernel,
        grid=grid,
        in_specs=[col, col, col, col,
                  mat(Ia), mat(Ia), mat(If), mat(If),
                  mat(Ja), mat(Ja), mat(Jf), mat(Jf)],
        out_specs=col,
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.int32),
        interpret=interpret,
    )(nra, nrf, nsa, nsf, ras, ral, rfs, rfl, sas, sal, sfs, sfl)
