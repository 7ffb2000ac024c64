"""Pallas TPU kernel: RI ALIGNEDAND (paper §3.3) on packed uint32 words.

The paper aligns two interval bitstrings byte-by-byte with carry-over and
ANDs them, early-exiting on the first non-zero byte. Byte loops are scalar
poison on TPU; here each grid program aligns a block of fragment pairs with
*vectorized 32-bit funnel shifts* over the whole word vector, applies the
optional XOR re-encoding mask (same-encoding joins) and the tail mask, and
reduces with a single any() per row.

Codes are packed LSB-first: stream bit ``3c+t`` is bit ``(3c+t) % 32`` of
word ``(3c+t) // 32`` (t = position inside the cell's 3-bit code). Fragments
start on cell boundaries, so the XOR mask's phase is always 0 and the mask
word pattern (period lcm(3,32) = 3 words) is passed in precomputed.

TPU layout: a block holds ``BB`` (= 8, the int32 sublane tile) pair rows
of ``W`` words (a multiple of 128 lanes). Every row has its own bit offset,
so the word alignment is a log-step barrel shifter — static lane rotations
by 1, 2, 4, ... words, each selected per row by one bit of the row's word
offset — with no lane gather. The rotation is circular over ``W``: callers
keep ``off_bits + n_bits <= 32 * W`` (a fragment never runs past its own
interval's words), so wrapped words never reach the tail mask.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["aligned_and_pallas", "BLOCK_ROWS"]

#: pair rows per grid step (the int32 sublane tile)
BLOCK_ROWS = 8


def _rotate_left(x, k: int):
    """Circular lane rotation: out[:, i] = x[:, (i + k) % W]."""
    W = x.shape[1]
    return pltpu.roll(x, (W - k) % W, 1)


def _funnel_align(words, off_bits):
    """Per-row extraction of the word stream starting at bit ``off_bits``
    ([BB, 1] int32) from ``words`` ([BB, W] uint32)."""
    W = words.shape[1]
    off_w = off_bits // 32
    cur = words
    step = 1
    while step < W:                       # barrel shifter over word offsets
        take = ((off_w // step) % 2) == 1
        cur = jnp.where(take, _rotate_left(cur, step), cur)
        step *= 2
    nxt = _rotate_left(cur, 1)
    sh = (off_bits % 32).astype(jnp.uint32)
    hi_sh = (jnp.uint32(32) - sh) % jnp.uint32(32)
    return (cur >> sh) | jnp.where(sh == 0, jnp.uint32(0), nxt << hi_sh)


def _kernel(meta_ref, x_ref, y_ref, mask_ref, out_ref):
    # meta rows: [BB, 4] int32 = (x_off_bits, y_off_bits, n_bits, xor_y)
    meta = meta_ref[...]
    x_off = meta[:, 0:1]
    y_off = meta[:, 1:2]
    n_bits = meta[:, 2:3]
    xor_y = meta[:, 3:4]

    ax = _funnel_align(x_ref[...], x_off)
    ay = _funnel_align(y_ref[...], y_off)
    ay = jnp.where(xor_y != 0, ay ^ mask_ref[...], ay)

    # tail mask: word k keeps bits [0, clamp(n_bits - 32k, 0, 32))
    k = jax.lax.broadcasted_iota(jnp.int32, ax.shape, 1)
    rem = jnp.clip(n_bits - 32 * k, 0, 32)
    tail = (jnp.uint32(1) << jnp.minimum(rem, 31).astype(jnp.uint32)) \
        - jnp.uint32(1)
    keep = jnp.where(rem >= 32, jnp.uint32(0xFFFFFFFF), tail)

    hit = ((ax & ay & keep) != 0).astype(jnp.int32)
    out_ref[...] = jnp.max(hit, axis=1, keepdims=True)


def aligned_and_pallas(x_words, y_words, meta, mask_words, *,
                       interpret: bool = False):
    """[B, 1] int32 0/1. x_words/y_words: [B, W] uint32 with B a multiple of
    ``BLOCK_ROWS`` and W of 128; meta: [B, 4] int32 (x_off_bits,
    y_off_bits, n_bits, xor_y); mask_words: [1, W] uint32."""
    B, W = x_words.shape
    assert B % BLOCK_ROWS == 0 and W % 128 == 0, (B, W)
    rows = pl.BlockSpec((BLOCK_ROWS, W), lambda b: (b, 0))
    return pl.pallas_call(
        _kernel,
        grid=(B // BLOCK_ROWS,),
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, 4), lambda b: (b, 0)),
            rows,
            rows,
            pl.BlockSpec((1, W), lambda b: (0, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, 1), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.int32),
        interpret=interpret,
    )(meta, x_words, y_words, mask_words)
