"""Refinement: exact geometry tests for indecisive candidate pairs (the
final stage of the paper's §2 pipeline, dominating end-to-end join cost).

The batched refinement subsystem (DESIGN.md §7), mirroring the batched
candidate generation (§8), filtering (§3), and construction (§6) passes. All three refinement
variants — polygon x polygon ``intersects`` (also serving ``selection``),
``within``, and linestring x polygon — have dataset-level batched
formulations over vertex-count **bucketed** pair batches: pairs group by the
power-of-two class of their Er x Es orientation-tile size (the same
padding-waste lever as the §4 interval-width bucketing), so padding waste
stays <= 2x and the [N, Er, Es] working set stays bounded.

Backends (``refine_backend`` in :class:`~repro.spatial.plan.JoinPlan`):

* ``sequential`` — the per-pair f64 reference loop over the
  :mod:`repro.core.geometry` oracles (``refine_*_seq``); every batched
  backend must be verdict-identical to it.
* ``numpy`` — vectorized host pass with the CMBR optimization of
  Aghajarian et al. [2]: only edges overlapping the pair's common MBR take
  part in the segment sweep (mask-based pruning, exact — a crossing or
  touch point lies in both MBRs, so no contributing edge is ever pruned).
  Containment resolves branch-free via representative interior points
  classified with closed-region PiP (no per-pair Python fallback loop).
* ``jnp`` — the same pass jit-compiled on device under ``enable_x64``.
  XLA contracts mul+add chains into FMAs (below the HLO level, so
  ``optimization_barrier`` cannot stop it), which can flip near-zero
  orientation signs vs strict IEEE; every sign test therefore carries a
  guard band, and pairs with any borderline evaluation re-run on host —
  final verdicts are identical to the sequential oracle.
* ``pallas`` — the edge x edge orientation sweep runs through
  ``kernels/refine`` in f32 with a relative guard band: definite verdicts
  are taken from the device, near-degenerate pairs come back *uncertain*
  and are re-checked on host at f64, so definite verdicts never contradict
  the f64 oracle.
"""
from __future__ import annotations

import numpy as np

from ..core import geometry
from ..core.geometry import polygon_edges, segments_intersect, size_buckets
from ..kernels import note_routed, to_device

__all__ = [
    "REFINE_BACKENDS", "refine", "refine_pair",
    "refine_pairs", "refine_within_pairs", "refine_line_poly_pairs",
    "refine_pairs_seq", "refine_within_pairs_seq",
    "refine_line_poly_pairs_seq", "device_chunk_rows", "map_row_chunks",
]

REFINE_BACKENDS = ("numpy", "jnp", "pallas", "sequential")

#: bound on the padded [N, Er, Es] orientation working set per bucket chunk
_CHUNK_ELEMS = 1 << 20
#: routed-row counter of guard-band pairs re-checked at host f64
_ESCALATED = "refine_escalated_rows_host"
#: bound on the padded [C, Er, Es] orientation tile of one device chunk
#: (fused and mesh refinement)
_DEVICE_CHUNK_ELEMS = 1 << 25


def _check_backend(backend: str) -> None:
    if backend not in REFINE_BACKENDS:
        raise ValueError(f"unknown refine backend {backend!r}; "
                         f"expected one of {REFINE_BACKENDS}")


# ---------------------------------------------------------------------------
# Sequential per-pair references (the verdict oracle)
# ---------------------------------------------------------------------------

def refine_pair(R, i: int, S, j: int) -> bool:
    return geometry.polygons_intersect(R.verts[i], R.nverts[i],
                                       S.verts[j], S.nverts[j])


def refine_pairs_seq(R, S, pairs: np.ndarray) -> np.ndarray:
    """Per-pair f64 reference for exact polygon intersection, [N,2] -> [N]."""
    pairs = np.asarray(pairs, np.int64).reshape(-1, 2)
    if len(pairs) == 0:
        return np.zeros(0, bool)
    return np.asarray([
        geometry.polygons_intersect(R.verts[i], R.nverts[i],
                                    S.verts[j], S.nverts[j])
        for i, j in pairs], bool)


def refine_within_pairs_seq(R, S, pairs: np.ndarray) -> np.ndarray:
    """Per-pair f64 reference for exact 'r within s', [N,2] -> [N]."""
    pairs = np.asarray(pairs, np.int64).reshape(-1, 2)
    if len(pairs) == 0:
        return np.zeros(0, bool)
    return np.asarray([
        geometry.polygon_within(R.verts[i], R.nverts[i],
                                S.verts[j], S.nverts[j])
        for i, j in pairs], bool)


def refine_line_poly_pairs_seq(L, S, pairs: np.ndarray) -> np.ndarray:
    """Per-pair f64 reference for linestring x polygon intersection."""
    pairs = np.asarray(pairs, np.int64).reshape(-1, 2)
    out = np.zeros(len(pairs), bool)
    for k, (li, pj) in enumerate(pairs):
        line = L.verts[li, : L.nverts[li]]
        poly = S.verts[pj, : S.nverts[pj]]
        a0, a1 = line[:-1], line[1:]
        b0 = poly
        b1 = np.roll(poly, -1, axis=0)
        crossed = bool(segments_intersect(
            a0[:, None, :], a1[:, None, :],
            b0[None, :, :], b1[None, :, :]).any())
        out[k] = crossed or bool(
            geometry.points_in_polygon_closed(line[:1], poly)[0])
    return out


# ---------------------------------------------------------------------------
# Shared batched pieces
# ---------------------------------------------------------------------------

def _chain_edges(verts: np.ndarray, nverts: np.ndarray):
    """Open-chain edges: (starts [N,V-1,2], ends, mask). Edge i runs vertex
    i -> i+1; the ring-closing edge of :func:`polygon_edges` is absent."""
    starts = verts[:, :-1]
    ends = verts[:, 1:]
    mask = np.arange(verts.shape[1] - 1)[None, :] < (nverts[:, None] - 1)
    return starts, ends, mask


def _cmbr_mask(mr: np.ndarray, ms: np.ndarray, e0, e1):
    """Edges overlapping the pair's common MBR (inclusive — exact pruning)."""
    cm = np.stack([np.maximum(mr[:, 0], ms[:, 0]),
                   np.maximum(mr[:, 1], ms[:, 1]),
                   np.minimum(mr[:, 2], ms[:, 2]),
                   np.minimum(mr[:, 3], ms[:, 3])], axis=1)     # [N,4]
    lo = np.minimum(e0, e1)                                     # [N,V,2]
    hi = np.maximum(e0, e1)
    return ((lo[..., 0] <= cm[:, None, 2]) & (hi[..., 0] >= cm[:, None, 0])
            & (lo[..., 1] <= cm[:, None, 3]) & (hi[..., 1] >= cm[:, None, 1]))


def _pip_batch_np(points, pmask, b0, b1, bm):
    """Closed-region PiP of per-pair point sets against per-pair polygons.

    points [N,M,2] (pmask [N,M]) vs polygon edges [N,V,...]. Returns
    (inside_or_on [N,M]) with masked points reported True (vacuous)."""
    x = points[..., 0][:, :, None]                              # [N,M,1]
    y = points[..., 1][:, :, None]
    x0, y0 = b0[..., 0][:, None, :], b0[..., 1][:, None, :]     # [N,1,V]
    x1, y1 = b1[..., 0][:, None, :], b1[..., 1][:, None, :]
    m = bm[:, None, :]
    cond = (y0 <= y) != (y1 <= y)
    with np.errstate(divide="ignore", invalid="ignore"):
        t = (y - y0) / np.where(y1 == y0, 1.0, y1 - y0)
    xint = x0 + t * (x1 - x0)
    inside = (np.sum(cond & (xint > x) & m, axis=2) % 2) == 1
    d = (x1 - x0) * (y - y0) - (y1 - y0) * (x - x0)
    onb = ((d == 0)
           & (np.minimum(x0, x1) <= x) & (x <= np.maximum(x0, x1))
           & (np.minimum(y0, y1) <= y) & (y <= np.maximum(y0, y1)) & m)
    return inside | onb.any(axis=2) | ~pmask


def _reps(D, idx: np.ndarray) -> np.ndarray:
    """Representative interior points for the selected polygons, [K,2]."""
    ui, inv = np.unique(np.asarray(idx, np.int64), return_inverse=True)
    return geometry.representative_points(D.verts[ui], D.nverts[ui])[inv]


def _compact_edges(e0, e1, mask):
    """Left-pack the masked-in edges of each row: [N,V,2] -> [N,K,2] with
    K = max kept per row. Pruned edges cannot contribute a crossing (the
    CMBR test is inclusive and exact), so sweeping the compacted arrays is
    result-identical while shrinking the [N, Er, Es] orientation tile by
    the prune rate on both axes. Low prune rates (< 1/4 of the padded
    width) skip the gather — the sweep saves less than the repacking
    costs."""
    K = max(1, int(mask.sum(axis=1).max()))
    if K >= mask.shape[1] * 3 // 4:
        return e0, e1, mask
    order = np.argsort(~mask, axis=1, kind="stable")
    take = order[:, :K]
    return (np.take_along_axis(e0, take[..., None], axis=1),
            np.take_along_axis(e1, take[..., None], axis=1),
            np.take_along_axis(mask, take, axis=1))


def _proper_cross_np(a0, a1, am, b0, b1, bm) -> np.ndarray:
    """Any *proper* (transversal, all orientations nonzero) edge crossing."""
    d1 = geometry._orient(b0[:, None, :, 0], b0[:, None, :, 1],
                          b1[:, None, :, 0], b1[:, None, :, 1],
                          a0[:, :, None, 0], a0[:, :, None, 1])
    d2 = geometry._orient(b0[:, None, :, 0], b0[:, None, :, 1],
                          b1[:, None, :, 0], b1[:, None, :, 1],
                          a1[:, :, None, 0], a1[:, :, None, 1])
    d3 = geometry._orient(a0[:, :, None, 0], a0[:, :, None, 1],
                          a1[:, :, None, 0], a1[:, :, None, 1],
                          b0[:, None, :, 0], b0[:, None, :, 1])
    d4 = geometry._orient(a0[:, :, None, 0], a0[:, :, None, 1],
                          a1[:, :, None, 0], a1[:, :, None, 1],
                          b1[:, None, :, 0], b1[:, None, :, 1])
    proper = (((d1 > 0) != (d2 > 0)) & ((d3 > 0) != (d4 > 0))
              & (d1 != 0) & (d2 != 0) & (d3 != 0) & (d4 != 0))
    return (proper & am[:, :, None] & bm[:, None, :]).any(axis=(1, 2))


# ---------------------------------------------------------------------------
# numpy batched cores (one vertex-count bucket at a time)
# ---------------------------------------------------------------------------

def _sweep_pruned(a0, a1, am, b0, b1, bm, mr, ms,
                  use_cmbr: bool) -> np.ndarray:
    """Any-segment-crossing per row, with CMBR pruning: rows where either
    side loses all its edges cannot cross (exact — every crossing or touch
    point lies in both MBRs), and the survivors sweep compacted tiles."""
    if not use_cmbr:
        hit = segments_intersect(a0[:, :, None, :], a1[:, :, None, :],
                                 b0[:, None, :, :], b1[:, None, :, :])
        return (hit & am[:, :, None] & bm[:, None, :]).any(axis=(1, 2))
    ams = am & _cmbr_mask(mr, ms, a0, a1)
    bms = bm & _cmbr_mask(mr, ms, b0, b1)
    crossed = np.zeros(len(a0), bool)
    live = ams.any(axis=1) & bms.any(axis=1)
    if live.any():
        a0c, a1c, amc = _compact_edges(a0[live], a1[live], ams[live])
        b0c, b1c, bmc = _compact_edges(b0[live], b1[live], bms[live])
        hit = segments_intersect(a0c[:, :, None, :], a1c[:, :, None, :],
                                 b0c[:, None, :, :], b1c[:, None, :, :])
        crossed[live] = (hit & amc[:, :, None]
                         & bmc[:, None, :]).any(axis=(1, 2))
    return crossed


def _intersects_batch_np(vr, nr, vs, ns, rep_r, rep_s, mr, ms,
                         use_cmbr: bool) -> np.ndarray:
    a0, a1, am = polygon_edges(vr, nr)
    b0, b1, bm = polygon_edges(vs, ns)
    crossed = _sweep_pruned(a0, a1, am, b0, b1, bm, mr, ms, use_cmbr)
    # containment (no crossing): representative point of either side inside
    # the closed other — sound unconditionally, complete when not crossed;
    # PiP parity needs the full (unpruned) edge set
    ones = np.ones((len(vr), 1), bool)
    in_s = _pip_batch_np(rep_r[:, None, :], ones, b0, b1, bm)[:, 0]
    in_r = _pip_batch_np(rep_s[:, None, :], ones, a0, a1, am)[:, 0]
    return crossed | in_s | in_r


def _within_batch_np(vr, nr, vs, ns, mr, ms, use_cmbr: bool) -> np.ndarray:
    """Staged 'r within s': exact MBR vertex prefilter -> closed PiP of the
    surviving rows -> proper-crossing sweep of the all-inside rows only.
    Each stage is exact, so the staging never changes verdicts — it only
    skips tensor work the sequential reference short-circuits past."""
    N = len(vr)
    out = np.zeros(N, bool)
    pmask = np.arange(vr.shape[1])[None, :] < nr[:, None]
    x, y = vr[..., 0], vr[..., 1]
    inmbr = (((x >= ms[:, None, 0]) & (x <= ms[:, None, 2])
              & (y >= ms[:, None, 1]) & (y <= ms[:, None, 3])) | ~pmask)
    cand = inmbr.all(axis=1)          # a vertex outside MBR(s) decides False
    if not cand.any():
        return out
    b0, b1, bm = polygon_edges(vs[cand], ns[cand])
    all_in = _pip_batch_np(vr[cand], pmask[cand], b0, b1, bm).all(axis=1)
    if not all_in.any():
        return out
    keep = np.nonzero(cand)[0][all_in]
    a0, a1, am = polygon_edges(vr[keep], nr[keep])
    b0, b1, bm = b0[all_in], b1[all_in], bm[all_in]
    if use_cmbr:
        a0, a1, am = _compact_edges(
            a0, a1, am & _cmbr_mask(mr[keep], ms[keep], a0, a1))
        b0, b1, bm = _compact_edges(
            b0, b1, bm & _cmbr_mask(mr[keep], ms[keep], b0, b1))
    out[keep] = ~_proper_cross_np(a0, a1, am, b0, b1, bm)
    return out


def _line_batch_np(vl, nl, vs, ns, mr, ms, use_cmbr: bool) -> np.ndarray:
    a0, a1, am = _chain_edges(vl, nl)
    b0, b1, bm = polygon_edges(vs, ns)
    head_in = _pip_batch_np(vl[:, :1], np.ones((len(vl), 1), bool),
                            b0, b1, bm)[:, 0]
    crossed = _sweep_pruned(a0, a1, am, b0, b1, bm, mr, ms, use_cmbr)
    return crossed | head_in


# ---------------------------------------------------------------------------
# jnp cores (device twins of the numpy cores). XLA contracts mul+add chains
# into FMAs below the HLO level (optimization_barrier does not stop it), so
# near-zero orientation/parity signs can differ from the strict-IEEE numpy
# path. Every sign-critical comparison therefore carries a guard band: pairs
# with any borderline evaluation come back *uncertain* and re-run on host,
# making the final jnp verdicts identical to the sequential oracle.
# ---------------------------------------------------------------------------

#: relative guard half-width for jit'd f64 sign tests — a few hundred ulps,
#: far above any FMA-contraction delta, far below general-position margins
_EPS_GUARD = 2.0 ** -44


def _orient_unc_jnp(ax, ay, bx, by, cx, cy):
    """(orientation, borderline) — borderline flags magnitudes within the
    FMA guard band of zero, where the jit'd sign may disagree with numpy.
    When either product is exactly zero the fused evaluation is provably
    identical to strict IEEE (the fma reduces to a single rounding of the
    other term), so axis-aligned geometry — whose orientations vanish
    through exact zeros — is exempt and does not escalate."""
    import jax.numpy as jnp
    p1 = (bx - ax) * (cy - ay)
    p2 = (by - ay) * (cx - ax)
    d = p1 - p2
    unc = ((jnp.abs(d) <= _EPS_GUARD * (jnp.abs(p1) + jnp.abs(p2)))
           & (p1 != 0) & (p2 != 0))
    return d, unc


def _edges_jnp(verts, nverts):
    import jax.numpy as jnp
    V = verts.shape[1]
    idx = jnp.arange(V)[None, :]
    valid = idx < nverts[:, None]
    nxt = jnp.where(valid, (idx + 1) % jnp.maximum(nverts[:, None], 1), 0)
    starts = jnp.where(valid[..., None], verts, verts[:, :1, :])
    ends = jnp.take_along_axis(
        verts, jnp.broadcast_to(nxt[..., None], nxt.shape + (2,)), axis=1)
    ends = jnp.where(valid[..., None], ends, verts[:, :1, :])
    return starts, ends, valid


def _chain_edges_jnp(verts, nverts):
    import jax.numpy as jnp
    mask = jnp.arange(verts.shape[1] - 1)[None, :] < (nverts[:, None] - 1)
    return verts[:, :-1], verts[:, 1:], mask


def _quad_orients_jnp(a0, a1, b0, b1):
    d1, u1 = _orient_unc_jnp(b0[..., 0], b0[..., 1], b1[..., 0], b1[..., 1],
                             a0[..., 0], a0[..., 1])
    d2, u2 = _orient_unc_jnp(b0[..., 0], b0[..., 1], b1[..., 0], b1[..., 1],
                             a1[..., 0], a1[..., 1])
    d3, u3 = _orient_unc_jnp(a0[..., 0], a0[..., 1], a1[..., 0], a1[..., 1],
                             b0[..., 0], b0[..., 1])
    d4, u4 = _orient_unc_jnp(a0[..., 0], a0[..., 1], a1[..., 0], a1[..., 1],
                             b1[..., 0], b1[..., 1])
    return (d1, d2, d3, d4), (u1 | u2 | u3 | u4)


def _segments_intersect_jnp(a0, a1, b0, b1):
    """(hit, borderline) — broadcastable segment intersection + guard."""
    import jax.numpy as jnp
    (d1, d2, d3, d4), unc = _quad_orients_jnp(a0, a1, b0, b1)
    proper = (((d1 > 0) != (d2 > 0)) & ((d3 > 0) != (d4 > 0))
              & (d1 != 0) & (d2 != 0) & (d3 != 0) & (d4 != 0))

    def on_seg(p0, p1, r):
        return ((jnp.minimum(p0[..., 0], p1[..., 0]) <= r[..., 0])
                & (r[..., 0] <= jnp.maximum(p0[..., 0], p1[..., 0]))
                & (jnp.minimum(p0[..., 1], p1[..., 1]) <= r[..., 1])
                & (r[..., 1] <= jnp.maximum(p0[..., 1], p1[..., 1])))

    touch = (((d1 == 0) & on_seg(b0, b1, a0))
             | ((d2 == 0) & on_seg(b0, b1, a1))
             | ((d3 == 0) & on_seg(a0, a1, b0))
             | ((d4 == 0) & on_seg(a0, a1, b1)))
    return proper | touch, unc


def _pip_batch_jnp(points, pmask, b0, b1, bm):
    """(inside_or_on [N,M], borderline [N,M]) closed-region PiP + guard."""
    import jax.numpy as jnp
    x = points[..., 0][:, :, None]
    y = points[..., 1][:, :, None]
    x0, y0 = b0[..., 0][:, None, :], b0[..., 1][:, None, :]
    x1, y1 = b1[..., 0][:, None, :], b1[..., 1][:, None, :]
    m = bm[:, None, :]
    cond = (y0 <= y) != (y1 <= y)
    step = ((y - y0) / jnp.where(y1 == y0, 1.0, y1 - y0)) * (x1 - x0)
    xint = x0 + step
    # step == 0 exactly (e.g. vertical edges) makes the fused add exact
    near = ((jnp.abs(xint - x)
             <= _EPS_GUARD * (jnp.abs(x0) + jnp.abs(step) + jnp.abs(x)))
            & (step != 0))
    inside = (jnp.sum(cond & (xint > x) & m, axis=2) % 2) == 1
    d, du = _orient_unc_jnp(x0, y0, x1, y1, x, y)
    inbox = ((jnp.minimum(x0, x1) <= x) & (x <= jnp.maximum(x0, x1))
             & (jnp.minimum(y0, y1) <= y) & (y <= jnp.maximum(y0, y1)) & m)
    onb = (d == 0) & inbox
    unc = ((cond & near & m) | (du & inbox)).any(axis=2) & pmask
    return inside | onb.any(axis=2) | ~pmask, unc


def _intersects_impl_jnp(vr, nr, vs, ns, rep_r, rep_s):
    """Pure-jnp batched intersects core (also the shard_map step body).

    Returns (verdicts [N], uncertain [N]) — uncertain pairs had a borderline
    sign evaluation and must be re-run on host."""
    import jax.numpy as jnp
    a0, a1, am = _edges_jnp(vr, nr)
    b0, b1, bm = _edges_jnp(vs, ns)
    hit, hunc = _segments_intersect_jnp(a0[:, :, None, :], a1[:, :, None, :],
                                        b0[:, None, :, :], b1[:, None, :, :])
    pair_mask = am[:, :, None] & bm[:, None, :]
    crossed = (hit & pair_mask).any(axis=(1, 2))
    ones = jnp.ones((vr.shape[0], 1), bool)
    in_s, u1 = _pip_batch_jnp(rep_r[:, None, :], ones, b0, b1, bm)
    in_r, u2 = _pip_batch_jnp(rep_s[:, None, :], ones, a0, a1, am)
    unc = (hunc & pair_mask).any(axis=(1, 2)) | u1[:, 0] | u2[:, 0]
    # a True reached through a non-borderline element holds on host too —
    # no need to escalate, whatever else is borderline
    definite_true = ((hit & ~hunc & pair_mask).any(axis=(1, 2))
                     | (in_s[:, 0] & ~u1[:, 0]) | (in_r[:, 0] & ~u2[:, 0]))
    return crossed | in_s[:, 0] | in_r[:, 0], unc & ~definite_true


def _within_impl_jnp(vr, nr, vs, ns):
    """(verdicts [N], uncertain [N]) batched 'r within s' on device."""
    import jax.numpy as jnp
    a0, a1, am = _edges_jnp(vr, nr)
    b0, b1, bm = _edges_jnp(vs, ns)
    pmask = jnp.arange(vr.shape[1])[None, :] < nr[:, None]
    in_b, pip_unc = _pip_batch_jnp(vr, pmask, b0, b1, bm)
    all_in = in_b.all(axis=1)
    (d1, d2, d3, d4), ounc = _quad_orients_jnp(
        a0[:, :, None, :], a1[:, :, None, :],
        b0[:, None, :, :], b1[:, None, :, :])
    proper = (((d1 > 0) != (d2 > 0)) & ((d3 > 0) != (d4 > 0))
              & (d1 != 0) & (d2 != 0) & (d3 != 0) & (d4 != 0))
    pair_mask = am[:, :, None] & bm[:, None, :]
    proper = (proper & pair_mask).any(axis=(1, 2))
    # a certainly-not-all-inside pair is False whatever the sweep says
    pu = pip_unc.any(axis=1)
    unc = pu | (all_in & (ounc & pair_mask).any(axis=(1, 2)))
    return all_in & ~proper, unc


def _line_impl_jnp(vl, nl, vs, ns):
    """(verdicts [N], uncertain [N]) linestring x polygon on device."""
    import jax.numpy as jnp
    a0, a1, am = _chain_edges_jnp(vl, nl)
    b0, b1, bm = _edges_jnp(vs, ns)
    hit, hunc = _segments_intersect_jnp(a0[:, :, None, :], a1[:, :, None, :],
                                        b0[:, None, :, :], b1[:, None, :, :])
    pair_mask = am[:, :, None] & bm[:, None, :]
    crossed = (hit & pair_mask).any(axis=(1, 2))
    ones = jnp.ones((vl.shape[0], 1), bool)
    head_in, hu = _pip_batch_jnp(vl[:, :1], ones, b0, b1, bm)
    unc = (hunc & pair_mask).any(axis=(1, 2)) | hu[:, 0]
    definite_true = ((hit & ~hunc & pair_mask).any(axis=(1, 2))
                     | (head_in[:, 0] & ~hu[:, 0]))
    return crossed | head_in[:, 0], unc & ~definite_true


def device_chunk_rows(Va: int, Vb: int) -> int:
    """Rows per device refinement chunk: the power of two that keeps the
    padded [C, Va, Vb] orientation tile within ``_DEVICE_CHUNK_ELEMS``."""
    by_mem = max(8, _DEVICE_CHUNK_ELEMS // max(1, Va * Vb))
    return 1 << int(np.floor(np.log2(by_mem)))


def map_row_chunks(body, C: int, *rows):
    """Apply a batched device core (rows -> per-row outputs) to [N, ...]
    operands ``C`` rows at a time with ``lax.map``: the [C, Er, Es] working
    set stays bounded whatever N is, and the body compiles once. Traced
    inside a jit or shard_map step."""
    import jax
    import jax.numpy as jnp
    N = rows[0].shape[0]
    if N <= C:
        return body(*rows)
    m = -(-N // C)
    pad = m * C - N
    chunks = tuple(
        jnp.pad(r, [(0, pad)] + [(0, 0)] * (r.ndim - 1)).reshape(
            (m, C) + r.shape[1:]) for r in rows)
    outs = jax.lax.map(lambda xs: body(*xs), chunks)
    return tuple(o.reshape((m * C,) + o.shape[2:])[:N] for o in outs)


_JNP_REFINE_JIT: dict | None = None


def _refine_jnp(kind: str, *arrays) -> tuple[np.ndarray, np.ndarray]:
    """Run a jit'd device core; returns (verdicts, uncertain) as numpy."""
    global _JNP_REFINE_JIT
    import jax
    with jax.enable_x64(True):
        if _JNP_REFINE_JIT is None:
            _JNP_REFINE_JIT = {
                "intersects": jax.jit(_intersects_impl_jnp),
                "within": jax.jit(_within_impl_jnp),
                "line": jax.jit(_line_impl_jnp),
            }
        res, unc = _JNP_REFINE_JIT[kind](*arrays)
        return np.array(res), np.asarray(unc)     # res: writable copy


# ---------------------------------------------------------------------------
# pallas: f32 device sweep + f64 host escalation of uncertain pairs
# ---------------------------------------------------------------------------

def _pallas_sweep(a0, a1, am, b0, b1, bm):
    """f32 device sweep of [N, E, 2] f64 edge sets -> (hit, unc) [N].

    Each row is translated (in f64) to its first A vertex before the f32
    cast: orientations are translation-invariant, and a pair's edges then
    sit within its common neighbourhood instead of ~1 from the origin, so
    the cast keeps ~1e3x more relative precision and the guard band's
    magnitude term shrinks with it — far fewer pairs come back uncertain.
    """
    from ..kernels import interpret_mode, pad_rows_pow2
    from ..kernels.refine import batch_edges_intersect
    origin = a0[:, :1, :]
    arrays, n = pad_rows_pow2([a0 - origin, a1 - origin, am,
                               b0 - origin, b1 - origin, bm])
    hit, unc = batch_edges_intersect(*arrays, interpret=interpret_mode())
    return np.asarray(hit)[:n], np.asarray(unc)[:n]


# ---------------------------------------------------------------------------
# Bucketed public drivers
# ---------------------------------------------------------------------------

def _bucketed(nvr: np.ndarray, nvs: np.ndarray, fn) -> np.ndarray:
    """Run ``fn(sel, Va, Vb) -> bool[len(sel)]`` over power-of-two buckets of
    the per-pair Er x Es tile size (padding waste <= 2x in the product)."""
    out = np.zeros(len(nvr), bool)
    sizes = np.maximum(nvr, 1) * np.maximum(nvs, 1)
    for sel in size_buckets(sizes, _CHUNK_ELEMS):
        Va = int(nvr[sel].max())
        Vb = int(nvs[sel].max())
        out[sel] = fn(sel, Va, Vb)
    return out


def refine_pairs(R, S, pairs: np.ndarray, use_cmbr: bool = True,
                 backend: str = "numpy") -> np.ndarray:
    """Exact intersection for candidate pairs [N,2] -> [N] bool, batched over
    vertex-count buckets on the selected backend."""
    _check_backend(backend)
    pairs = np.asarray(pairs, np.int64).reshape(-1, 2)
    if len(pairs) == 0:
        return np.zeros(0, bool)
    if backend == "sequential":
        return refine_pairs_seq(R, S, pairs)
    nvr = R.nverts[pairs[:, 0]]
    nvs = S.nverts[pairs[:, 1]]
    rep_r = _reps(R, pairs[:, 0])
    rep_s = _reps(S, pairs[:, 1])

    def run(sel, Va, Vb):
        p = pairs[sel]
        vr = R.verts[:, :Va][p[:, 0]]
        vs = S.verts[:, :Vb][p[:, 1]]
        nr, ns = nvr[sel], nvs[sel]
        if backend == "jnp":
            res, unc = _refine_jnp("intersects", vr, nr, vs, ns,
                                   rep_r[sel], rep_s[sel])
            note_routed(_ESCALATED, np.count_nonzero(unc))
            if unc.any():   # borderline signs: re-run on host (strict IEEE)
                res[unc] = _intersects_batch_np(
                    vr[unc], nr[unc], vs[unc], ns[unc],
                    rep_r[sel][unc], rep_s[sel][unc],
                    R.mbrs[p[unc, 0]], S.mbrs[p[unc, 1]], use_cmbr)
            return res
        if backend == "pallas":
            return _refine_pallas_intersects(
                R, S, p, vr, nr, vs, ns, rep_r[sel], rep_s[sel], use_cmbr)
        return _intersects_batch_np(vr, nr, vs, ns, rep_r[sel], rep_s[sel],
                                    R.mbrs[p[:, 0]], S.mbrs[p[:, 1]],
                                    use_cmbr)

    return _bucketed(nvr, nvs, run)


def _refine_pallas_intersects(R, S, p, vr, nr, vs, ns, rep_r, rep_s,
                              use_cmbr) -> np.ndarray:
    a0, a1, am = polygon_edges(vr, nr)
    b0, b1, bm = polygon_edges(vs, ns)
    ams, bms = am, bm
    if use_cmbr:
        ams = am & _cmbr_mask(R.mbrs[p[:, 0]], S.mbrs[p[:, 1]], a0, a1)
        bms = bm & _cmbr_mask(R.mbrs[p[:, 0]], S.mbrs[p[:, 1]], b0, b1)
    hit, unc = _pallas_sweep(a0, a1, ams, b0, b1, bms)
    note_routed(_ESCALATED, np.count_nonzero(unc))
    out = hit & ~unc
    # no definite crossing: containment via host closed-PiP of the reps
    rest = ~hit & ~unc
    if rest.any():
        ones = np.ones((int(rest.sum()), 1), bool)
        in_s = _pip_batch_np(rep_r[rest][:, None, :], ones,
                             b0[rest], b1[rest], bm[rest])[:, 0]
        in_r = _pip_batch_np(rep_s[rest][:, None, :], ones,
                             a0[rest], a1[rest], am[rest])[:, 0]
        out[rest] = in_s | in_r
    # guard band tripped: full f64 re-check on host
    if unc.any():
        out[unc] = refine_pairs(R, S, p[unc], use_cmbr=use_cmbr,
                                backend="numpy")
    return out


def refine_within_pairs(R, S, pairs: np.ndarray,
                        backend: str = "numpy") -> np.ndarray:
    """Exact 'r within s' for candidate pairs [N,2] -> [N] bool, batched."""
    _check_backend(backend)
    pairs = np.asarray(pairs, np.int64).reshape(-1, 2)
    if len(pairs) == 0:
        return np.zeros(0, bool)
    if backend == "sequential":
        return refine_within_pairs_seq(R, S, pairs)
    nvr = R.nverts[pairs[:, 0]]
    nvs = S.nverts[pairs[:, 1]]

    def run(sel, Va, Vb):
        p = pairs[sel]
        vr = R.verts[:, :Va][p[:, 0]]
        vs = S.verts[:, :Vb][p[:, 1]]
        nr, ns = nvr[sel], nvs[sel]
        if backend == "jnp":
            res, unc = _refine_jnp("within", vr, nr, vs, ns)
            note_routed(_ESCALATED, np.count_nonzero(unc))
            if unc.any():
                res[unc] = _within_batch_np(
                    vr[unc], nr[unc], vs[unc], ns[unc],
                    R.mbrs[p[unc, 0]], S.mbrs[p[unc, 1]], True)
            return res
        if backend == "pallas":
            a0, a1, am = polygon_edges(vr, nr)
            b0, b1, bm = polygon_edges(vs, ns)
            hit, unc = _pallas_sweep(a0, a1, am, b0, b1, bm)
            note_routed(_ESCALATED, np.count_nonzero(unc))
            out = np.zeros(len(p), bool)       # definite crossing: not within
            todo = ~hit | unc
            if todo.any():
                out[todo] = _within_batch_np(
                    vr[todo], nr[todo], vs[todo], ns[todo],
                    R.mbrs[p[todo, 0]], S.mbrs[p[todo, 1]], True)
            return out
        return _within_batch_np(vr, nr, vs, ns, R.mbrs[p[:, 0]],
                                S.mbrs[p[:, 1]], True)

    return _bucketed(nvr, nvs, run)


def refine_line_poly_pairs(L, S, pairs: np.ndarray,
                           backend: str = "numpy") -> np.ndarray:
    """Exact linestring x polygon intersection for [N,2] (line, poly) pairs,
    batched over vertex-count buckets on the selected backend."""
    _check_backend(backend)
    pairs = np.asarray(pairs, np.int64).reshape(-1, 2)
    if len(pairs) == 0:
        return np.zeros(0, bool)
    if backend == "sequential":
        return refine_line_poly_pairs_seq(L, S, pairs)
    nvl = L.nverts[pairs[:, 0]]
    nvs = S.nverts[pairs[:, 1]]

    def run(sel, Va, Vb):
        p = pairs[sel]
        vl = L.verts[:, :Va][p[:, 0]]
        vs = S.verts[:, :Vb][p[:, 1]]
        nl, ns = nvl[sel], nvs[sel]
        if backend == "jnp":
            res, unc = _refine_jnp("line", vl, nl, vs, ns)
            note_routed(_ESCALATED, np.count_nonzero(unc))
            if unc.any():
                res[unc] = _line_batch_np(
                    vl[unc], nl[unc], vs[unc], ns[unc],
                    L.mbrs[p[unc, 0]], S.mbrs[p[unc, 1]], True)
            return res
        if backend == "pallas":
            a0, a1, am = _chain_edges(vl, nl)
            b0, b1, bm = polygon_edges(vs, ns)
            hit, unc = _pallas_sweep(a0, a1, am, b0, b1, bm)
            note_routed(_ESCALATED, np.count_nonzero(unc))
            out = hit & ~unc
            rest = ~hit & ~unc
            if rest.any():
                out[rest] = _pip_batch_np(
                    vl[rest][:, :1], np.ones((int(rest.sum()), 1), bool),
                    b0[rest], b1[rest], bm[rest])[:, 0]
            if unc.any():
                out[unc] = _line_batch_np(
                    vl[unc], nl[unc], vs[unc], ns[unc],
                    L.mbrs[p[unc, 0]], S.mbrs[p[unc, 1]], False)
            return out
        return _line_batch_np(vl, nl, vs, ns, L.mbrs[p[:, 0]],
                              S.mbrs[p[:, 1]], True)

    return _bucketed(nvl, nvs, run)


def refine(R, S, pairs: np.ndarray, predicate: str = "intersects",
           backend: str = "numpy") -> np.ndarray:
    """Predicate dispatcher: one entry point for all refinement variants.

    ``selection`` shares the intersects refinement (query polygons as S)."""
    if predicate == "within":
        return refine_within_pairs(R, S, pairs, backend=backend)
    if predicate == "linestring":
        return refine_line_poly_pairs(R, S, pairs, backend=backend)
    if predicate not in ("intersects", "selection"):
        raise ValueError(f"unknown predicate {predicate!r}; expected one of "
                         "('intersects', 'within', 'linestring', "
                         "'selection')")
    return refine_pairs(R, S, pairs, backend=backend)


# ---------------------------------------------------------------------------
# Fused-chain device refinement (DESIGN.md §12)
# ---------------------------------------------------------------------------

def device_geometry(D, kind: str = "polygon") -> dict:
    """f64 device copies of a dataset's padded vertex tensors, plus (for
    polygons) representative interior points for every object.

    Uploaded once per dataset and cached on the handle (the
    ``_interval_lists_cache`` idiom of ``core.join``), so fused chains and
    warm service groups gather by index instead of re-packing host slabs
    per query. The cache keys on the identity of the ``verts`` array —
    incremental dataset patches swap the array and naturally invalidate.
    """
    import jax
    key = (id(D.verts), kind)
    cached = getattr(D, "_device_geom", None)
    if cached is not None and cached[0] == key:
        return cached[1]
    with jax.enable_x64(True):
        geom = {
            "verts": to_device(np.asarray(D.verts, np.float64)),
            "nverts": to_device(np.asarray(D.nverts, np.int32)),
        }
        if kind != "line":
            reps = geometry.representative_points(D.verts, D.nverts)
            geom["reps"] = to_device(np.asarray(reps, np.float64))
    try:
        D._device_geom = (key, geom)
    except AttributeError:      # slotted handle: still correct, just colder
        pass
    return geom


_FUSED_REFINE_FNS: dict = {}


def _fused_refine_fn(kind: str, C: int):
    """jit'd chunked refinement of a front-packed pair prefix.

    The packed frame is walked in chunks of ``C`` by a ``lax.while_loop``
    that stops at the first chunk past the device survivor count — the
    work scales with the (data-dependent) survivor count without the count
    ever visiting the host, and the refinement body is compiled once, not
    once per chunk.
    """
    import jax
    import jax.numpy as jnp

    if (kind, C) in _FUSED_REFINE_FNS:
        return _FUSED_REFINE_FNS[(kind, C)]

    def run(vr_all, nr_all, rep_r, vs_all, ns_all, rep_s, ri, si,
            perm, count):
        Np = perm.shape[0]
        lane = jnp.arange(C, dtype=jnp.int32)

        def chunk(state):
            c0, res, unc = state
            idx = jax.lax.dynamic_slice(perm, (c0,), (C,))
            take = (c0 + lane) < count
            rr = ri[idx]
            ss = si[idx]
            vr, nr = vr_all[rr], nr_all[rr]
            vs, ns = vs_all[ss], ns_all[ss]
            if kind == "intersects":
                v, u = _intersects_impl_jnp(vr, nr, vs, ns,
                                            rep_r[rr], rep_s[ss])
            elif kind == "within":
                v, u = _within_impl_jnp(vr, nr, vs, ns)
            else:
                v, u = _line_impl_jnp(vr, nr, vs, ns)
            res = jax.lax.dynamic_update_slice(res, v & take, (c0,))
            unc = jax.lax.dynamic_update_slice(unc, u & take, (c0,))
            return c0 + C, res, unc

        state = (jnp.zeros((), jnp.int32), jnp.zeros(Np, bool),
                 jnp.zeros(Np, bool))
        _, res, unc = jax.lax.while_loop(lambda st: st[0] < count, chunk,
                                         state)
        return res, unc

    _FUSED_REFINE_FNS[(kind, C)] = jax.jit(run)
    return _FUSED_REFINE_FNS[(kind, C)]


def fused_refine_lanes(R, S, ri_dev, si_dev, perm, count,
                       predicate: str = "intersects"):
    """Device (res, unc) lanes over a front-packed indecisive prefix.

    ``perm``/``count`` come from ``kernels.compact.compact_mask`` over the
    INDECISIVE status lane; ``ri_dev``/``si_dev`` are the device pair frame
    (at least ``len(perm)`` rows; callers pad it to a power of two).
    Returns [Np] bool lanes in the *packed* frame (``Np`` = ``len(perm)``
    padded up to the chunk size, padding entries False); scatter back
    through ``perm``. ``unc`` marks FMA-borderline pairs for the single
    end-of-chain host escalation — identical to the staged jnp backend's
    per-bucket escalation set.
    """
    import jax
    import jax.numpy as jnp

    kind = {"intersects": "intersects", "selection": "intersects",
            "within": "within", "linestring": "line"}[predicate]
    geom_r = device_geometry(R, kind="line" if kind == "line" else "polygon")
    geom_s = device_geometry(S)
    N = perm.shape[0]
    if N == 0:
        return jnp.zeros(0, bool), jnp.zeros(0, bool), perm
    # chunk size: a bounded [C, Er, Es] tile; the packed frame pads to a
    # power of two (a multiple of C), so the program compiles once per
    # size class of the frame, not per frame length
    Va = int(np.asarray(R.nverts).max(initial=1))
    Vb = int(np.asarray(S.nverts).max(initial=1))
    Np = 1 << int(np.ceil(np.log2(max(N, 8))))
    C = min(Np, device_chunk_rows(Va, Vb))
    # pad the permutation with out-of-frame indices: the scatter back into
    # candidate-frame lanes drops them (mode='drop')
    perm_p = jnp.concatenate(
        [perm, jnp.full(Np - N, N, jnp.int32)]) if Np != N else perm
    with jax.enable_x64(True):
        fn = _fused_refine_fn(kind, C)
        res, unc = fn(geom_r["verts"], geom_r["nverts"],
                      geom_r.get("reps"), geom_s["verts"],
                      geom_s["nverts"], geom_s.get("reps"),
                      ri_dev, si_dev, perm_p, count)
    return res, unc, perm_p
