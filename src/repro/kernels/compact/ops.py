"""jit'd public wrapper for the on-device compaction primitive.

``compact_mask`` is the stage-boundary operator of the fused pipeline
(DESIGN.md §12): it turns a device bool lane into a stable front-pack
permutation plus a device survivor count, so the next stage can gather the
compacted prefix without the mask ever visiting the host.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .. import interpret_mode, note_routed
from .compact import BLOCK_ROWS, LANES, exclusive_scan_pallas

__all__ = ["compact_mask"]

#: one pallas launch walks the lane in 1,024-element blocks, serially on
#: the chip; lanes longer than 16k blocks take the (identical) cumsum path
#: (counted in the routed-row counters) rather than a longer serial walk
_PALLAS_MAX = 1 << 24


@partial(jax.jit, static_argnames=("backend", "interpret"))
def _compact_impl(mask, *, backend: str, interpret: bool):
    N = mask.shape[0]
    m = mask.astype(jnp.int32)
    if backend == "pallas":
        tile = BLOCK_ROWS * LANES
        Np = -(-N // tile) * tile
        m2d = jnp.pad(m, (0, Np - N)).reshape(-1, LANES)
        excl2d, total = exclusive_scan_pallas(m2d, interpret=interpret)
        excl = excl2d.reshape(-1)[:N]
        k = total[0]
    else:
        c = jnp.cumsum(m)
        excl = c - m
        k = c[-1]
    i = jnp.arange(N, dtype=jnp.int32)
    # selected rows pack to [0, k) in order; unselected to [k, N) in order —
    # dest is a permutation, so the scatter is collision-free
    dest = jnp.where(m > 0, excl, k + (i - excl))
    perm = jnp.zeros(N, jnp.int32).at[dest].set(i)
    return perm, k.astype(jnp.int32)


def compact_mask(mask, *, backend: str = "jnp"):
    """Stable front-pack of a device bool lane: (perm [N] int32, count []).

    ``perm[:count]`` are the True indices ascending, ``perm[count:]`` the
    False indices ascending — gathering ``lane[perm]`` front-packs stage
    survivors entirely on device; ``count`` stays a device scalar (the
    fused chain never reads it on host). ``backend='pallas'`` runs the
    blocked SMEM-carry scan kernel (interpret mode off-TPU); ``'jnp'`` the
    plain cumsum. Both are bit-identical to ``ref.compact_mask_ref``.
    """
    if mask.shape[0] == 0:
        return jnp.zeros(0, jnp.int32), jnp.zeros((), jnp.int32)
    if backend == "pallas" and mask.shape[0] > _PALLAS_MAX:
        note_routed("compact_long_lane_rows_jnp", mask.shape[0])
        backend = "jnp"
    return _compact_impl(mask, backend=backend, interpret=interpret_mode())
