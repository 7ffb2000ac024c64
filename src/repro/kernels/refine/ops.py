"""Wrapper: pad edge batches to tile multiples and dispatch."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .refine import edges_intersect_pallas


def _pad(a, axis, mult, fill):
    size = a.shape[axis]
    target = ((size + mult - 1) // mult) * mult
    if target == size:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, target - size)
    return jnp.pad(a, pad, constant_values=fill)


@partial(jax.jit, static_argnames=("interpret", "eps"))
def batch_edges_intersect(a0, a1, am, b0, b1, bm, *, eps=1e-5, interpret=False):
    """(hit, uncertain) [B] for padded edge batches of any B/Ea/Eb."""
    B = a0.shape[0]
    a0 = _pad(jnp.asarray(a0, jnp.float32), 1, 128, 0.0)
    a1 = _pad(jnp.asarray(a1, jnp.float32), 1, 128, 0.0)
    am = _pad(jnp.asarray(am, bool), 1, 128, False)
    b0 = _pad(jnp.asarray(b0, jnp.float32), 1, 128, 0.0)
    b1 = _pad(jnp.asarray(b1, jnp.float32), 1, 128, 0.0)
    bm = _pad(jnp.asarray(bm, bool), 1, 128, False)
    arrs = [_pad(x, 0, 8, 0) for x in (a0, a1)] + [_pad(am, 0, 8, False)] \
        + [_pad(x, 0, 8, 0) for x in (b0, b1)] + [_pad(bm, 0, 8, False)]
    hit, unc = edges_intersect_pallas(*arrs, eps=eps, interpret=interpret)
    return hit[:B, 0] != 0, unc[:B, 0] != 0
