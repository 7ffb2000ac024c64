"""Wrapper: packs host RI bit fragments into word batches and dispatches.

Also provides :func:`pack_bits_u32` / :func:`xor_mask_words` used by tests
and by the RI device pipeline.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .ri_and import BLOCK_ROWS, aligned_and_pallas


def pack_bits_u32(bits: np.ndarray, W: int) -> np.ndarray:
    """[n] 0/1 -> [W] uint32 words, LSB-first within each word."""
    out = np.zeros(W, np.uint32)
    n = min(len(bits), 32 * W)
    idx = np.arange(n)
    np.add.at(out, idx // 32,
              (bits[:n].astype(np.uint32) << (idx % 32).astype(np.uint32)))
    return out


def xor_mask_words(W: int, pattern=(1, 1, 0)) -> np.ndarray:
    """Repeating 3-bit XOR mask (phase 0) packed into W uint32 words."""
    bits = np.tile(np.asarray(pattern, np.uint8), (32 * W + 2) // 3)[: 32 * W]
    return pack_bits_u32(bits, W)


def _pad_to(a, axis, mult):
    size = a.shape[axis]
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, -(-size // mult) * mult - size)
    return jnp.pad(a, pad)


@partial(jax.jit, static_argnames=("interpret",))
def batch_aligned_and(x_words, y_words, meta, mask_words, *, interpret=False):
    """[B] bool ALIGNEDAND verdicts; pads rows to the kernel's block and
    words to whole 128-lane vectors (zero words never AND non-zero)."""
    B = x_words.shape[0]

    def words(w):
        return _pad_to(_pad_to(jnp.asarray(w, jnp.uint32), 1, 128),
                       0, BLOCK_ROWS)

    out = aligned_and_pallas(
        words(x_words), words(y_words),
        _pad_to(jnp.asarray(meta, jnp.int32), 0, BLOCK_ROWS),
        _pad_to(jnp.asarray(mask_words, jnp.uint32)[None, :], 1, 128),
        interpret=interpret)
    return out[:B, 0] != 0
