"""Plain reference of the polygon ``intersects`` predicate, and the
comparison that decides ``correct``.

Two closed polygons intersect when a boundary segment of one meets a
boundary segment of the other (touching counts), or when one lies inside
the other. Without a boundary contact, one polygon is inside the other
exactly when any of its vertices is, so the test is: any segment pair
meets, or the first vertex of either ring lies inside or on the other.

The reference is brute force over every pair whose MBRs meet, in numpy,
on the benchmark's own copy of the geometry, for every R object. It
imports nothing of the program and uses none of its tables. ``mode``
chooses the arithmetic: ``exact`` is float64, the stated precision;
``f32`` is the control, the same test one precision below.
"""
from __future__ import annotations

import numpy as np

MODES = ("exact", "f32")

#: bound on the [pairs, Va, Vb] working set of one block
_BLOCK_ELEMS = 1 << 22


def mbr_pairs(mbrs_r: np.ndarray, mbrs_s: np.ndarray) -> np.ndarray:
    """Every (r id, s id) [K, 2] whose closed MBRs meet, by brute force
    in blocks of R rows, in row-major order."""
    out = []
    step = max(1, _BLOCK_ELEMS // max(1, len(mbrs_s)))
    for i in range(0, len(mbrs_r), step):
        r = mbrs_r[i:i + step]
        hit = ((mbrs_s[None, :, 0] <= r[:, None, 2])
               & (mbrs_s[None, :, 2] >= r[:, None, 0])
               & (mbrs_s[None, :, 1] <= r[:, None, 3])
               & (mbrs_s[None, :, 3] >= r[:, None, 1]))
        ri, si = np.nonzero(hit)
        out.append(np.stack([ri + i, si], axis=1))
    return (np.concatenate(out).astype(np.int64) if out
            else np.zeros((0, 2), np.int64))


def _orient(ax, ay, bx, by, cx, cy):
    return (bx - ax) * (cy - ay) - (by - ay) * (cx - ax)


def _on_segment(ax, ay, bx, by, cx, cy):
    """Whether collinear point c lies within the box of segment ab."""
    return ((np.minimum(ax, bx) <= cx) & (cx <= np.maximum(ax, bx))
            & (np.minimum(ay, by) <= cy) & (cy <= np.maximum(ay, by)))


def _ring_edges(v: np.ndarray, n: np.ndarray):
    """Edge starts and ends [B, V, 2] and their mask [B, V]."""
    V = v.shape[1]
    idx = np.arange(V)[None, :]
    valid = idx < n[:, None]
    nxt = np.where(valid, (idx + 1) % np.maximum(n[:, None], 1), 0)
    ends = np.take_along_axis(v, nxt[..., None].repeat(2, axis=2), axis=1)
    return v, ends, valid


def _any_contact(vr, nr, vs, ns) -> np.ndarray:
    a0, a1, am = _ring_edges(vr, nr)
    b0, b1, bm = _ring_edges(vs, ns)
    ax, ay = a0[:, :, None, 0], a0[:, :, None, 1]
    bx, by = a1[:, :, None, 0], a1[:, :, None, 1]
    cx, cy = b0[:, None, :, 0], b0[:, None, :, 1]
    dx, dy = b1[:, None, :, 0], b1[:, None, :, 1]
    o1 = _orient(ax, ay, bx, by, cx, cy)
    o2 = _orient(ax, ay, bx, by, dx, dy)
    o3 = _orient(cx, cy, dx, dy, ax, ay)
    o4 = _orient(cx, cy, dx, dy, bx, by)
    meet = ((((o1 > 0) & (o2 < 0)) | ((o1 < 0) & (o2 > 0)))
            & (((o3 > 0) & (o4 < 0)) | ((o3 < 0) & (o4 > 0))))
    if ((o1 == 0) | (o2 == 0) | (o3 == 0) | (o4 == 0)).any():
        meet |= (o1 == 0) & _on_segment(ax, ay, bx, by, cx, cy)
        meet |= (o2 == 0) & _on_segment(ax, ay, bx, by, dx, dy)
        meet |= (o3 == 0) & _on_segment(cx, cy, dx, dy, ax, ay)
        meet |= (o4 == 0) & _on_segment(cx, cy, dx, dy, bx, by)
    return (meet & am[:, :, None] & bm[:, None, :]).any(axis=(1, 2))


def _inside_or_on(p: np.ndarray, v: np.ndarray, n: np.ndarray) -> np.ndarray:
    """Whether point p[b] lies inside or on ring b (even-odd rule)."""
    e0, e1, em = _ring_edges(v, n)
    x, y = p[:, None, 0], p[:, None, 1]
    x0, y0, x1, y1 = e0[..., 0], e0[..., 1], e1[..., 0], e1[..., 1]
    crosses = (y0 <= y) != (y1 <= y)
    with np.errstate(divide="ignore", invalid="ignore"):
        xint = x0 + (y - y0) * (x1 - x0) / np.where(crosses, y1 - y0, 1)
    inside = (np.sum(crosses & (xint > x) & em, axis=1) % 2) == 1
    on = ((_orient(x0, y0, x1, y1, x, y) == 0)
          & _on_segment(x0, y0, x1, y1, x, y) & em).any(axis=1)
    return inside | on


def intersects(vr, nr, vs, ns, dtype=np.float64) -> np.ndarray:
    """Exact ``intersects`` of ring pairs (vr[b], vs[b]) in ``dtype``."""
    vr = np.asarray(vr, dtype)
    vs = np.asarray(vs, dtype)
    return (_any_contact(vr, nr, vs, ns)
            | _inside_or_on(vr[:, 0], vs, ns)
            | _inside_or_on(vs[:, 0], vr, nr))


def pairs(r: tuple, s: tuple, mode: str = "exact") -> np.ndarray:
    """Reference result pairs (r id, s id) [K, 2] over every R object.
    ``r`` and ``s`` are (verts, nverts, mbrs) of each side.

    Candidates are grouped by their exact pair of vertex counts, so no
    block computes padding, and each group runs in blocks of at most
    ``_BLOCK_ELEMS`` edge pairs."""
    if mode not in MODES:
        raise ValueError(f"unknown reference mode {mode!r}; one of {MODES}")
    dtype = np.float32 if mode == "f32" else np.float64
    (vr, nr, mr), (vs, ns, ms) = r, s
    cand = mbr_pairs(mr, ms)
    if len(cand) == 0:
        return cand
    na, nb = nr[cand[:, 0]], ns[cand[:, 1]]
    order = np.lexsort((nb, na))
    cand, na, nb = cand[order], na[order], nb[order]
    cut = np.flatnonzero((np.diff(na) != 0) | (np.diff(nb) != 0)) + 1
    keep = np.zeros(len(cand), bool)
    for g0, g1 in zip(np.r_[0, cut], np.r_[cut, len(cand)]):
        va, vb = int(na[g0]), int(nb[g0])
        step = max(1, _BLOCK_ELEMS // (va * vb))
        for b0 in range(g0, g1, step):
            i, j = cand[b0:min(b0 + step, g1)].T
            keep[b0:b0 + len(i)] = intersects(
                vr[i, :va], nr[i], vs[j, :vb], ns[j], dtype)
    return cand[keep]


def _keys(p: np.ndarray, n_s: int) -> np.ndarray:
    p = np.asarray(p, np.int64).reshape(-1, 2)
    return p[:, 0] * n_s + p[:, 1]


def compare(got: np.ndarray, want: np.ndarray, n_s: int) -> dict:
    """Missing and extra pairs of ``got`` against ``want``, and the pairs
    ``got`` repeats; both are [K, 2] with S ids below ``n_s``."""
    g = _keys(got, n_s)
    uniq = np.unique(g)
    w = np.unique(_keys(want, n_s))
    return {"missing": int(len(np.setdiff1d(w, uniq, assume_unique=True))),
            "extra": int(len(np.setdiff1d(uniq, w, assume_unique=True))),
            "repeated": int(len(g) - len(uniq))}
