"""Candidate generation: the batched partitioned MBR join (paper §2,
DESIGN.md §8).

First of the four pipeline stages (MBR filter -> intermediate filter ->
construction-backed verdicts -> refinement): produce every (r, s) pair
whose MBRs intersect, duplicate-free, without materializing the dense
[N, M] cross test. The algorithm is the partition-bucket approach of
Tsitsigkos & Mamoulis [49] with reference-point duplicate elimination
[13]: MBRs are hashed into a coarse uniform grid over the *joint data
extent*, co-bucketed pairs are cross-tested, and a qualifying pair is
emitted only from the bucket containing the bottom-left corner of the
pair's common MBR.

Batching contract (the ``mbr_backend`` knob on
:class:`~repro.spatial.plan.JoinPlan`, mirroring ``build_backend`` /
``refine_backend``):

* ``sequential`` — the per-object expansion loop and per-bucket cross-test
  walk (the pre-batching reference, order-identical to it); every batched
  backend must produce the identical pair *set*.
* ``numpy`` — fully vectorized: bucket expansion via repeat/cumsum offset
  arithmetic, a sort-merge join over the two flat (object, bucket) tables,
  and one vectorized intersection + reference-point ownership mask over
  the co-bucket cross-product rows. No per-object or per-bucket Python.
* ``jnp`` — the same candidate rows evaluated on device: the mask pass
  (MBR gathers, interval tests, integer ownership test) is jit-compiled
  over padded row batches. ``spatial.distributed.distributed_mbr_join``
  shards the identical mask pass over the mesh 'data' axis.

The grid granularity adapts to the data (Kipf et al., *Adaptive Geospatial
Joins*): :func:`adaptive_grid` picks the finest power-of-two grid whose
bucket-expansion stays within a constant factor of the object count, so
cross-tests shrink as far as linear-size bucket tables allow. A fixed
grid remains available (``mbr_grid`` on ``JoinPlan``). Bucketing
normalizes by the joint extent of both datasets — raw coordinates are
*not* assumed to lie in the unit square.

The reference-point bucket is computed from the per-object integer cell
ranges (``floor`` and ``clip`` are monotone, so the common MBR's cell is
exactly the elementwise max of the two low cells) — bucketing and
ownership can never disagree through float rounding, on any backend.
"""
from __future__ import annotations

import numpy as np

from ..kernels import pad_rows_pow2, to_device

__all__ = [
    "MBR_BACKENDS", "mbr_join", "mbr_intersect_mask", "adaptive_grid",
    "joint_extent", "bucket_ranges", "expand_buckets", "candidate_rows",
    "pair_mask_body", "pair_mask_lane_jnp", "MBRIndex",
]

MBR_BACKENDS = ("numpy", "jnp", "sequential")

#: bucket-entry budget per object for the adaptive grid (expansion stays
#: within this factor of the object count)
_ENTRY_BUDGET = 8
_MAX_GRID = 1024


def _check_backend(backend: str) -> None:
    if backend not in MBR_BACKENDS:
        raise ValueError(f"unknown mbr backend {backend!r}; "
                         f"expected one of {MBR_BACKENDS}")


def _resolve_grid(grid, mbrs_r, mbrs_s, extent) -> int:
    """Validate an explicit grid (``>= 1``) or pick one adaptively."""
    if grid is None:
        return adaptive_grid(mbrs_r, mbrs_s, extent)
    if int(grid) < 1:
        raise ValueError(f"mbr grid must be >= 1 or None (adaptive), "
                         f"got {grid!r}")
    return int(grid)


def _prepare(mbrs_r: np.ndarray, mbrs_s: np.ndarray, grid: int | None):
    """Shared host preamble of every ``mbr_join`` entry point: coerce,
    guard empties, resolve the joint extent and grid. Returns
    (mbrs_r, mbrs_s, k, extent), with ``k = 0`` signalling an empty join —
    keeping host and mesh paths pair-set-identical by construction."""
    mbrs_r = np.asarray(mbrs_r, np.float64).reshape(-1, 4)
    mbrs_s = np.asarray(mbrs_s, np.float64).reshape(-1, 4)
    extent = joint_extent(mbrs_r, mbrs_s)
    # resolve even when a side is empty: an invalid explicit grid must
    # raise regardless of which partition it is first wired through
    k = _resolve_grid(grid, mbrs_r, mbrs_s, extent)
    if len(mbrs_r) == 0 or len(mbrs_s) == 0:
        return mbrs_r, mbrs_s, 0, extent
    return mbrs_r, mbrs_s, k, extent


def mbr_intersect_mask(mr: np.ndarray, ms: np.ndarray) -> np.ndarray:
    """Pairwise MBR intersection for [N,4] x [M,4] -> [N,M] bool.

    The brute-force oracle: every ``mbr_join`` backend must return exactly
    its nonzero set (asserted by ``tests/test_mbr_join.py``).
    """
    return ((mr[:, None, 0] <= ms[None, :, 2]) & (ms[None, :, 0] <= mr[:, None, 2])
            & (mr[:, None, 1] <= ms[None, :, 3]) & (ms[None, :, 1] <= mr[:, None, 3]))


# ---------------------------------------------------------------------------
# Grid selection and bucketing
# ---------------------------------------------------------------------------

def joint_extent(mbrs_r: np.ndarray, mbrs_s: np.ndarray
                 ) -> tuple[float, float, float]:
    """(x0, y0, span) of the square window covering both datasets' MBRs.

    ``span`` is the larger side, floored at a tiny positive value so that
    degenerate (single-point) inputs still bucket without dividing by zero.
    """
    allm = np.concatenate([mbrs_r.reshape(-1, 4), mbrs_s.reshape(-1, 4)])
    if len(allm) == 0:
        return 0.0, 0.0, 1.0
    x0 = float(allm[:, 0].min())
    y0 = float(allm[:, 1].min())
    span = max(float(allm[:, 2].max()) - x0, float(allm[:, 3].max()) - y0)
    return x0, y0, max(span, np.finfo(np.float64).tiny)


def adaptive_grid(mbrs_r: np.ndarray, mbrs_s: np.ndarray,
                  extent: tuple[float, float, float] | None = None) -> int:
    """Grid granularity from MBR-extent statistics (Kipf-style adaptivity).

    Picks the finest power-of-two ``k`` (up to 1024) whose total bucket
    expansion ``sum_i (w_i*k + 1)(h_i*k + 1)`` stays within ``_ENTRY_BUDGET``
    entries per object: finer grids mean smaller buckets (fewer cross-test
    rows), while the budget keeps the expanded tables linear in the input,
    so neither side of the hash join can degenerate — large objects push
    ``k`` down, many small objects allow it up.
    """
    mbrs_r = np.asarray(mbrs_r, np.float64).reshape(-1, 4)
    mbrs_s = np.asarray(mbrs_s, np.float64).reshape(-1, 4)
    n = len(mbrs_r) + len(mbrs_s)
    if n == 0:
        return 1
    span = (extent or joint_extent(mbrs_r, mbrs_s))[2]
    allm = np.concatenate([mbrs_r, mbrs_s])
    w = (allm[:, 2] - allm[:, 0]) / span
    h = (allm[:, 3] - allm[:, 1]) / span
    ks = 2 ** np.arange(0, int(np.log2(_MAX_GRID)) + 1)
    entries = ((w[:, None] * ks + 1.0) * (h[:, None] * ks + 1.0)).sum(axis=0)
    ok = np.nonzero(entries <= _ENTRY_BUDGET * n)[0]
    return int(ks[ok[-1]]) if len(ok) else 1


def bucket_ranges(mbrs: np.ndarray, k: int,
                  extent: tuple[float, float, float]) -> tuple[np.ndarray, np.ndarray]:
    """Inclusive cell range [x0,x1] x [y0,y1] per MBR on the k x k grid.

    Coordinates are normalized by the joint data ``extent`` before
    bucketing — MBRs far outside the unit square spread over the grid
    instead of all clamping into the border cells (the pre-§8 bug that
    degenerated translated/scaled workloads to one quadratic cross-test).
    """
    x0, y0, span = extent
    scaled = (mbrs.reshape(-1, 4) - [x0, y0, x0, y0]) / span * k
    lo = np.clip(np.floor(scaled[:, :2]).astype(np.int64), 0, k - 1)
    hi = np.clip(np.floor(scaled[:, 2:]).astype(np.int64), 0, k - 1)
    return lo, hi


# ---------------------------------------------------------------------------
# Batched core: vectorized expansion + sort-merge bucket join
# ---------------------------------------------------------------------------

def expand_buckets(lo: np.ndarray, hi: np.ndarray, k: int
                   ) -> tuple[np.ndarray, np.ndarray]:
    """Flat (object, bucket) table for inclusive cell ranges; vectorized.

    Row-major bucket ids ``x * k + y``; per-object cell offsets come from
    repeat/cumsum arithmetic — no Python loop over objects.
    """
    lo = lo.reshape(-1, 2)
    hi = hi.reshape(-1, 2)
    nx = hi[:, 0] - lo[:, 0] + 1
    ny = hi[:, 1] - lo[:, 1] + 1
    cnt = nx * ny
    total = int(cnt.sum())
    if total == 0:
        z = np.zeros(0, np.int64)
        return z, z
    obj = np.repeat(np.arange(len(lo), dtype=np.int64), cnt)
    start = np.cumsum(cnt) - cnt
    off = np.arange(total, dtype=np.int64) - start[obj]
    oy = off % ny[obj]
    ox = off // ny[obj]
    return obj, (lo[obj, 0] + ox) * k + (lo[obj, 1] + oy)


def _cross_rows(obj_r: np.ndarray, buck_r: np.ndarray,
                obj_s: np.ndarray, buck_s: np.ndarray
                ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Cartesian co-bucket rows of two *bucket-sorted* (object, bucket)
    tables: ``(ri, si, own)`` with ``own`` the shared bucket id. The single
    definition of the sort-merge tail, shared between the one-shot
    :func:`candidate_rows` and the warm :class:`MBRIndex` probe path."""
    ur, start_r, cnt_r = np.unique(buck_r, return_index=True,
                                   return_counts=True)
    us, start_s, cnt_s = np.unique(buck_s, return_index=True,
                                   return_counts=True)
    common, ir, is_ = np.intersect1d(ur, us, assume_unique=True,
                                     return_indices=True)
    cr = cnt_r[ir]
    cs = cnt_s[is_]
    m = cr * cs
    total = int(m.sum())
    if total == 0:
        z = np.zeros(0, np.int64)
        return z, z, z
    grp = np.repeat(np.arange(len(common), dtype=np.int64), m)
    off = np.arange(total, dtype=np.int64) - (np.cumsum(m) - m)[grp]
    a = off // cs[grp]
    b = off % cs[grp]
    ri = obj_r[start_r[ir][grp] + a]
    si = obj_s[start_s[is_][grp] + b]
    return ri, si, common[grp]


def candidate_rows(mbrs_r: np.ndarray, mbrs_s: np.ndarray, k: int,
                   extent: tuple[float, float, float]
                   ) -> tuple[np.ndarray, np.ndarray, np.ndarray,
                              np.ndarray, np.ndarray, np.ndarray]:
    """Co-bucket cross-product rows of the grid-hash join.

    Returns ``(ri, si, own_x, own_y, lo_r, lo_s)``: for every bucket shared
    by both sides, the cartesian rows of its R x S members (``ri``/``si``
    index the original datasets; ``own_x``/``own_y`` are the shared
    bucket's cell). A row is a join result iff the MBRs intersect *and*
    ``(max(lo_r[ri], lo_s[si]) == (own_x, own_y))`` — the reference-point
    ownership test, evaluated by the caller's backend of choice (host
    numpy, device jnp, or sharded over the mesh in
    ``distributed.distributed_mbr_join``).
    """
    lo_r, hi_r = bucket_ranges(mbrs_r, k, extent)
    lo_s, hi_s = bucket_ranges(mbrs_s, k, extent)
    obj_r, buck_r = expand_buckets(lo_r, hi_r, k)
    obj_s, buck_s = expand_buckets(lo_s, hi_s, k)

    order_r = np.argsort(buck_r, kind="stable")
    order_s = np.argsort(buck_s, kind="stable")
    obj_r, buck_r = obj_r[order_r], buck_r[order_r]
    obj_s, buck_s = obj_s[order_s], buck_s[order_s]

    ri, si, own = _cross_rows(obj_r, buck_r, obj_s, buck_s)
    if len(ri) == 0:
        z = np.zeros(0, np.int64)
        return z, z, z, z, lo_r, lo_s
    return ri, si, own // k, own % k, lo_r, lo_s


def pair_mask_body(xp, mbrs_r, mbrs_s, lo_r, lo_s, ri, si, own_x, own_y):
    """Intersection + reference-point ownership mask over candidate rows.

    The single definition of the pair test, generic over the array module
    (``numpy`` or ``jax.numpy``) — every backend, including the mesh step
    in ``spatial.distributed``, evaluates this body, so the test can never
    diverge between backends whose contract is pair-set identity.
    """
    a = mbrs_r[ri]
    b = mbrs_s[si]
    hit = ((a[:, 0] <= b[:, 2]) & (b[:, 0] <= a[:, 2])
           & (a[:, 1] <= b[:, 3]) & (b[:, 1] <= a[:, 3]))
    owner = ((xp.maximum(lo_r[ri, 0], lo_s[si, 0]) == own_x)
             & (xp.maximum(lo_r[ri, 1], lo_s[si, 1]) == own_y))
    return hit & owner


def _pair_mask_np(mbrs_r, mbrs_s, lo_r, lo_s, ri, si, own_x, own_y):
    return pair_mask_body(np, mbrs_r, mbrs_s, lo_r, lo_s, ri, si,
                          own_x, own_y)


_JNP_MASK = None


def pair_mask_lane_jnp(mbrs_r, mbrs_s, lo_r, lo_s, ri, si, own_x, own_y):
    """Device-resident pair mask: (lane [Npad] device bool, n).

    The same mask pass jit-compiled on device (f64 under ``enable_x64`` —
    without it JAX would silently round coordinates to f32 and merge
    nearby MBR borders), rows padded to powers of two so recompilation
    stays logarithmic in the row count. The lane never visits the host —
    the fused chain (DESIGN.md §12) consumes it directly as the
    CandidateSet ``valid`` lane; padding rows are already False via the
    jit's ``valid`` operand. ``lane[:n]`` are the real rows.
    """
    global _JNP_MASK
    import jax
    import jax.numpy as jnp

    if _JNP_MASK is None:
        def mask(mr, ms, lor, los, ri, si, ox, oy, valid):
            return pair_mask_body(jnp, mr, ms, lor, los, ri, si,
                                  ox, oy) & valid
        _JNP_MASK = jax.jit(mask)

    # the replicated tables pad too: their exact shapes would otherwise
    # retrigger a compile for every distinct dataset size (padded table
    # rows are only gathered by padded candidate rows, masked by `valid`)
    (mbrs_r, lo_r), _ = pad_rows_pow2([mbrs_r, lo_r])
    (mbrs_s, lo_s), _ = pad_rows_pow2([mbrs_s, lo_s])
    (ri, si, own_x, own_y, valid), n = pad_rows_pow2(
        [ri, si, own_x, own_y, np.ones(len(ri), bool)])
    with jax.enable_x64(True):
        out = _JNP_MASK(*(to_device(a) for a in (mbrs_r, mbrs_s, lo_r, lo_s,
                                                 ri, si, own_x, own_y,
                                                 valid)))
    return out, n


def _pair_mask_jnp(mbrs_r, mbrs_s, lo_r, lo_s, ri, si, own_x, own_y):
    """Staged-mode wrapper: compute the device lane, then materialize it
    through the chain's sanctioned sync point (``fused.to_host``)."""
    from .fused import to_host
    out, n = pair_mask_lane_jnp(mbrs_r, mbrs_s, lo_r, lo_s, ri, si,
                                own_x, own_y)
    return to_host(out)[:n]


# ---------------------------------------------------------------------------
# Sequential reference (the pre-batching per-object / per-bucket walk)
# ---------------------------------------------------------------------------

def _mbr_join_sequential(mbrs_r, mbrs_s, k, extent) -> np.ndarray:
    """Order-identical reference: per-object expansion loop, per-bucket
    cross test. Every batched backend must emit the identical pair set."""
    lo_r, hi_r = bucket_ranges(mbrs_r, k, extent)
    lo_s, hi_s = bucket_ranges(mbrs_s, k, extent)

    def expand(lo, hi):
        obj, bx, by = [], [], []
        for i in range(len(lo)):
            xs = np.arange(lo[i, 0], hi[i, 0] + 1)
            ys = np.arange(lo[i, 1], hi[i, 1] + 1)
            X, Y = np.meshgrid(xs, ys, indexing="ij")
            obj.append(np.full(X.size, i, np.int64))
            bx.append(X.ravel()); by.append(Y.ravel())
        if not obj:
            z = np.zeros(0, np.int64)
            return z, z
        return (np.concatenate(obj),
                np.concatenate(bx) * k + np.concatenate(by))

    obj_r, buck_r = expand(lo_r, hi_r)
    obj_s, buck_s = expand(lo_s, hi_s)

    order_r = np.argsort(buck_r, kind="stable")
    order_s = np.argsort(buck_s, kind="stable")
    obj_r, buck_r = obj_r[order_r], buck_r[order_r]
    obj_s, buck_s = obj_s[order_s], buck_s[order_s]

    pairs = []
    ur, idx_r = np.unique(buck_r, return_index=True)
    us, idx_s = np.unique(buck_s, return_index=True)
    common, ir, is_ = np.intersect1d(ur, us, return_indices=True)
    bounds_r = np.append(idx_r, len(buck_r))
    bounds_s = np.append(idx_s, len(buck_s))
    for c, a, b in zip(common, ir, is_):
        rs = obj_r[bounds_r[a]: bounds_r[a + 1]]
        ss = obj_s[bounds_s[b]: bounds_s[b + 1]]
        hit = mbr_intersect_mask(mbrs_r[rs], mbrs_s[ss])
        bx = np.maximum(lo_r[rs, None, 0], lo_s[None, ss, 0])
        by = np.maximum(lo_r[rs, None, 1], lo_s[None, ss, 1])
        owner = (bx * k + by) == c
        ii, jj = np.nonzero(hit & owner)
        if len(ii):
            pairs.append(np.stack([rs[ii], ss[jj]], axis=1))
    if not pairs:
        return np.zeros((0, 2), np.int64)
    return np.concatenate(pairs, axis=0)


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def mbr_join(mbrs_r: np.ndarray, mbrs_s: np.ndarray,
             grid: int | None = None, backend: str = "numpy") -> np.ndarray:
    """All (r, s) index pairs with intersecting MBRs. Returns [N,2] int64.

    ``grid=None`` (the default) picks the granularity adaptively from the
    MBR-extent statistics (:func:`adaptive_grid`); an explicit ``grid``
    pins it. ``backend`` selects the execution path (``MBR_BACKENDS``) —
    the pair set is identical for every backend and every grid.
    """
    _check_backend(backend)
    mbrs_r, mbrs_s, k, extent = _prepare(mbrs_r, mbrs_s, grid)
    if k == 0:
        return np.zeros((0, 2), np.int64)
    if backend == "sequential":
        return _mbr_join_sequential(mbrs_r, mbrs_s, k, extent)
    ri, si, own_x, own_y, lo_r, lo_s = candidate_rows(mbrs_r, mbrs_s, k,
                                                      extent)
    if len(ri) == 0:
        return np.zeros((0, 2), np.int64)
    mask_fn = _pair_mask_jnp if backend == "jnp" else _pair_mask_np
    keep = mask_fn(mbrs_r, mbrs_s, lo_r, lo_s, ri, si, own_x, own_y)
    return np.stack([ri[keep], si[keep]], axis=1)


# ---------------------------------------------------------------------------
# Warm index: build the R-side bucket table once, probe many times
# ---------------------------------------------------------------------------

class MBRIndex:
    """Grid-hash bucket table over one dataset's MBRs, built once and
    probed by many query batches (the serving path of DESIGN.md §10).

    A probe reuses the sorted (object, bucket) table instead of
    re-expanding and re-sorting the indexed side per join. The pair *set*
    is grid- and extent-invariant (``floor`` and ``clip`` are monotone, so
    the reference-point ownership cell — the elementwise max of the two
    clipped low cells — is covered by both objects' clipped cell ranges
    even when a query MBR lies outside the index extent), hence
    ``probe(q)`` equals ``mbr_join(self.mbrs, q)`` as a set for any grid.

    ``insert`` / ``delete`` splice only the affected buckets' entries
    (``stats["entries_touched"]`` counts them) — with the grid and extent
    pinned at construction, a patched index is array-identical to one
    freshly built over the patched MBRs with the same ``grid``/``extent``.
    """

    def __init__(self, mbrs: np.ndarray, grid: int | None = None,
                 extent: tuple[float, float, float] | None = None):
        self.mbrs = np.asarray(mbrs, np.float64).reshape(-1, 4).copy()
        self.extent = extent or joint_extent(self.mbrs, self.mbrs)
        self.k = _resolve_grid(grid, self.mbrs, self.mbrs, self.extent)
        self.lo, hi = bucket_ranges(self.mbrs, self.k, self.extent)
        obj, buck = expand_buckets(self.lo, hi, self.k)
        order = np.argsort(buck, kind="stable")
        self._obj, self._buck = obj[order], buck[order]
        self.stats = {"inserts": 0, "deletes": 0, "probes": 0,
                      "entries_touched": 0}

    @property
    def n_entries(self) -> int:
        return len(self._buck)

    def probe(self, mbrs_q: np.ndarray, backend: str = "numpy"
              ) -> np.ndarray:
        """All (indexed, query) pairs with intersecting MBRs, [N,2] int64 —
        pair-set-identical to ``mbr_join(self.mbrs, mbrs_q, backend)``."""
        _check_backend(backend)
        self.stats["probes"] += 1
        mbrs_q = np.asarray(mbrs_q, np.float64).reshape(-1, 4)
        if len(self.mbrs) == 0 or len(mbrs_q) == 0:
            return np.zeros((0, 2), np.int64)
        if backend == "sequential":
            return _mbr_join_sequential(self.mbrs, mbrs_q, self.k,
                                        self.extent)
        lo_q, hi_q = bucket_ranges(mbrs_q, self.k, self.extent)
        obj_q, buck_q = expand_buckets(lo_q, hi_q, self.k)
        order = np.argsort(buck_q, kind="stable")
        obj_q, buck_q = obj_q[order], buck_q[order]
        ri, si, own = _cross_rows(self._obj, self._buck, obj_q, buck_q)
        if len(ri) == 0:
            return np.zeros((0, 2), np.int64)
        mask_fn = _pair_mask_jnp if backend == "jnp" else _pair_mask_np
        keep = mask_fn(self.mbrs, mbrs_q, self.lo, lo_q, ri, si,
                       own // self.k, own % self.k)
        return np.stack([ri[keep], si[keep]], axis=1)

    def insert(self, mbr: np.ndarray) -> int:
        """Add one MBR; returns its index id. Only the new object's
        buckets gain entries (spliced at each bucket run's end, matching
        the obj-ascending order of a fresh build)."""
        mbr = np.asarray(mbr, np.float64).reshape(1, 4)
        new_id = len(self.mbrs)
        self.mbrs = np.concatenate([self.mbrs, mbr])
        lo, hi = bucket_ranges(mbr, self.k, self.extent)
        self.lo = np.concatenate([self.lo, lo])
        obj, buck = expand_buckets(lo, hi, self.k)
        pos = np.searchsorted(self._buck, buck, side="right")
        self._obj = np.insert(self._obj, pos, new_id)
        self._buck = np.insert(self._buck, pos, buck)
        self.stats["inserts"] += 1
        self.stats["entries_touched"] += len(buck)
        return new_id

    def delete(self, idx: int) -> None:
        """Remove the MBR at ``idx``; later ids shift down by one (the
        renumbering a fresh build over the remaining MBRs would use)."""
        if not 0 <= idx < len(self.mbrs):
            raise IndexError(f"MBRIndex.delete: id {idx} out of range "
                             f"[0, {len(self.mbrs)})")
        keep = self._obj != idx
        self.stats["entries_touched"] += int((~keep).sum())
        self._obj = self._obj[keep] - (self._obj[keep] > idx)
        self._buck = self._buck[keep]
        self.mbrs = np.delete(self.mbrs, idx, axis=0)
        self.lo = np.delete(self.lo, idx, axis=0)
        self.stats["deletes"] += 1
