"""Pallas TPU kernel: batched polygon-boundary intersection tests.

Refinement dominates the end-to-end spatial join (paper §2); its core is an
edge x edge segment-intersection sweep per candidate pair. Each grid program
evaluates a [BB, Ea, EB] tile of orientation predicates on the VPU
(coordinates split into separate x/y planes — a trailing dim of 2 would
waste (8,128) tiling).

f32 on device with an epsilon guard band: any orientation magnitude below
``eps`` (relative) makes the pair *uncertain* rather than decided; the
driver re-checks uncertain pairs on host at f64. Definite hits/misses never
contradict the exact predicate (tested against the f64 oracle).

TPU layout: edge masks arrive as int32 0/1 planes and the per-pair
verdicts leave as [B, 1] int32 columns — Mosaic neither tiles a rank-1
block of BB < 128 rows nor relayouts i1 vectors into the 3-D tile.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["edges_intersect_pallas"]


def _any_rows(pred):
    """[BB, Ea, EB] bool -> [BB, 1] int32 0/1 (any over the edge tile)."""
    return jnp.max(jnp.max(pred.astype(jnp.int32), axis=2), axis=1,
                   keepdims=True)


def _kernel(a0x_ref, a0y_ref, a1x_ref, a1y_ref, am_ref,
            b0x_ref, b0y_ref, b1x_ref, b1y_ref, bm_ref,
            hit_ref, unc_ref, *, eps):
    jb = pl.program_id(1)

    a0x = a0x_ref[...]; a0y = a0y_ref[...]       # [BB, Ea]
    a1x = a1x_ref[...]; a1y = a1y_ref[...]
    am = am_ref[...]
    b0x = b0x_ref[...]; b0y = b0y_ref[...]       # [BB, EB]
    b1x = b1x_ref[...]; b1y = b1y_ref[...]
    bm = bm_ref[...]

    def orient(px, py, qx, qy, rx, ry):
        return (qx - px) * (ry - py) - (qy - py) * (rx - px)

    A0x = a0x[:, :, None]; A0y = a0y[:, :, None]
    A1x = a1x[:, :, None]; A1y = a1y[:, :, None]
    B0x = b0x[:, None, :]; B0y = b0y[:, None, :]
    B1x = b1x[:, None, :]; B1y = b1y[:, None, :]

    d1 = orient(B0x, B0y, B1x, B1y, A0x, A0y)
    d2 = orient(B0x, B0y, B1x, B1y, A1x, A1y)
    d3 = orient(A0x, A0y, A1x, A1y, B0x, B0y)
    d4 = orient(A0x, A0y, A1x, A1y, B0x * 0 + B1x, B0y * 0 + B1y)

    valid = am[:, :, None] * bm[:, None, :] > 0
    proper = ((d1 > 0) != (d2 > 0)) & ((d3 > 0) != (d4 > 0))

    # relative guard band: |orient| below eps * scale * (scale + mag). The
    # scale^2 term covers f32 arithmetic rounding; the scale * mag term
    # covers the f64 -> f32 coordinate cast (an absolute perturbation
    # ~eps32 * |coord| which enters the orientation multiplied by the edge
    # length, so short edges far from the origin need the magnitude term).
    scale = (jnp.abs(A1x - A0x) + jnp.abs(A1y - A0y)
             + jnp.abs(B1x - B0x) + jnp.abs(B1y - B0y))
    mag = (jnp.maximum(jnp.abs(A0x), jnp.abs(A0y))
           + jnp.maximum(jnp.abs(B0x), jnp.abs(B0y)))
    tol = eps * scale * (scale + mag)
    near0 = (jnp.abs(d1) <= tol) | (jnp.abs(d2) <= tol) \
        | (jnp.abs(d3) <= tol) | (jnp.abs(d4) <= tol)
    # bounding boxes must overlap for a near-collinear touch to matter
    boxes = ((jnp.minimum(A0x, A1x) <= jnp.maximum(B0x, B1x) + tol)
             & (jnp.minimum(B0x, B1x) <= jnp.maximum(A0x, A1x) + tol)
             & (jnp.minimum(A0y, A1y) <= jnp.maximum(B0y, B1y) + tol)
             & (jnp.minimum(B0y, B1y) <= jnp.maximum(A0y, A1y) + tol))

    hit = _any_rows(proper & ~near0 & valid)
    unc = _any_rows(near0 & boxes & valid)

    @pl.when(jb == 0)
    def _():
        hit_ref[...] = hit
        unc_ref[...] = unc

    @pl.when(jb != 0)
    def _():
        hit_ref[...] = jnp.maximum(hit_ref[...], hit)
        unc_ref[...] = jnp.maximum(unc_ref[...], unc)


def edges_intersect_pallas(a0, a1, am, b0, b1, bm, *, eps: float = 1e-5,
                           block_b: int = 8, block_e: int = 128,
                           interpret: bool = False):
    """(hit [B, 1], uncertain [B, 1]) int32 0/1.

    a0/a1: [B, Ea, 2] f32; b0/b1: [B, Eb, 2]; am/bm: [B, Ea] / [B, Eb]
    int32 0/1 edge masks.
    """
    B, Ea, _ = a0.shape
    Eb = b0.shape[1]
    assert B % block_b == 0 and Eb % block_e == 0
    grid = (B // block_b, Eb // block_e)

    def split(p):
        return jnp.asarray(p[..., 0], jnp.float32), jnp.asarray(p[..., 1], jnp.float32)

    a0x, a0y = split(a0); a1x, a1y = split(a1)
    b0x, b0y = split(b0); b1x, b1y = split(b1)

    spec_a = pl.BlockSpec((block_b, Ea), lambda b, j: (b, 0))
    spec_b = pl.BlockSpec((block_b, block_e), lambda b, j: (b, j))
    spec_o = pl.BlockSpec((block_b, 1), lambda b, j: (b, 0))

    return pl.pallas_call(
        partial(_kernel, eps=eps),
        grid=grid,
        in_specs=[spec_a] * 4 + [spec_a] + [spec_b] * 4 + [spec_b],
        out_specs=(spec_o, spec_o),
        out_shape=(jax.ShapeDtypeStruct((B, 1), jnp.int32),
                   jax.ShapeDtypeStruct((B, 1), jnp.int32)),
        interpret=interpret,
    )(a0x, a0y, a1x, a1y, jnp.asarray(am, jnp.int32),
      b0x, b0y, b1x, b1y, jnp.asarray(bm, jnp.int32))
