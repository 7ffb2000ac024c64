"""jit'd public wrapper: pads to kernel tile multiples and dispatches."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .interval_join import april_trichotomy_pallas, interval_overlap_pallas

I32_MAX = np.iinfo(np.int32).max


def _pad_axis(a, axis, mult, fill):
    size = a.shape[axis]
    target = ((size + mult - 1) // mult) * mult
    if target == size:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, target - size)
    return jnp.pad(a, pad, constant_values=fill)


def _count_col(n, block_b):
    """[B] counts -> zero-padded [Bp, 1] int32 column (the kernel layout)."""
    return _pad_axis(jnp.asarray(n, jnp.int32), 0, block_b, 0)[:, None]


@partial(jax.jit, static_argnames=("interpret", "block_b", "block_j"))
def batch_interval_overlap(xs, xl, nx, ys, yl, ny, *, interpret: bool = False,
                           block_b: int = 8, block_j: int = 128):
    """Overlap verdicts [B] bool for padded interval batches (any I/J/B)."""
    xs = _pad_axis(jnp.asarray(xs, jnp.int32), 1, 128, I32_MAX)
    xl = _pad_axis(jnp.asarray(xl, jnp.int32), 1, 128, I32_MAX)
    ys = _pad_axis(jnp.asarray(ys, jnp.int32), 1, block_j, I32_MAX)
    yl = _pad_axis(jnp.asarray(yl, jnp.int32), 1, block_j, I32_MAX)
    B = xs.shape[0]
    xs = _pad_axis(xs, 0, block_b, I32_MAX)
    xl = _pad_axis(xl, 0, block_b, I32_MAX)
    ys = _pad_axis(ys, 0, block_b, I32_MAX)
    yl = _pad_axis(yl, 0, block_b, I32_MAX)
    out = interval_overlap_pallas(xs, xl, _count_col(nx, block_b), ys, yl,
                                  _count_col(ny, block_b),
                                  block_b=block_b, block_j=block_j,
                                  interpret=interpret)
    return out[:B, 0] != 0


@partial(jax.jit, static_argnames=("interpret", "block_b"))
def _trichotomy_jit(nra, nrf, nsa, nsf, mats, *, interpret, block_b):
    padded = []
    for s, l in mats:
        padded.append((_pad_axis(_pad_axis(jnp.asarray(s, jnp.int32), 1, 128,
                                           I32_MAX), 0, block_b, I32_MAX),
                       _pad_axis(_pad_axis(jnp.asarray(l, jnp.int32), 1, 128,
                                           I32_MAX), 0, block_b, I32_MAX)))
    counts = [_count_col(n, block_b) for n in (nra, nrf, nsa, nsf)]
    flat = [a for pair in padded for a in pair]
    return april_trichotomy_pallas(*counts, *flat, block_b=block_b,
                                   interpret=interpret)[:, 0]


def batch_april_trichotomy(ras, ral, nra, rfs, rfl, nrf,
                           sas, sal, nsa, sfs, sfl, nsf, *,
                           interpret: bool = False,
                           block_b: int = 8) -> np.ndarray:
    """Fused three-join verdicts [B] int8 for padded A/F batches (any
    widths/B; pads to kernel tile multiples and dispatches)."""
    B = ras.shape[0]
    out = _trichotomy_jit(nra, nrf, nsa, nsf,
                          ((ras, ral), (rfs, rfl), (sas, sal), (sfs, sfl)),
                          interpret=interpret, block_b=block_b)
    return np.asarray(out[:B]).astype(np.int8)
