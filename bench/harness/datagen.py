"""Seeded synthetic polygon layers: the benchmark's own copy of the
generator, so that a change to the program's data generation cannot move
the yardstick.

Polygons are star-shaped rings (sorted jittered angles, jittered radii)
around centres drawn from 16 clusters of a fixed map, so that layers built
from different seeds cover the same regions, as the TIGER layers of the
paper do. A layer is generated in chunks, each drawn by one vectorised
pass from a generator seeded on ``(spec name, seed, chunk index)``.

A deployment scales the spec table by ``k``: counts times ``k`` and radii
divided by ``sqrt(k)``, so that each polygon keeps its number of
neighbours, and the raster order grows with ``log4(k)`` so that each
polygon keeps its number of cells.

Digitised layers hold boundaries that nearly coincide: a park drawn along
a shore, a landmark on a county line. :func:`deployment` places a share
of the R polygons next to an S polygon, across a slab ``NEAR_GAP`` wide,
so that such a pair does not intersect, while float32 coordinates cannot
tell the two sides apart.
"""
from __future__ import annotations

import math
import zlib

import numpy as np

#: spec name -> (count, mean vertices, mean radius, radius jitter). Radii
#: are in units of the unit-square map; the ordering of mean MBR areas
#: (T2 < T1 < T3) follows the paper's Table 4.
SPECS: dict[str, tuple[int, int, float, float]] = {
    "T1": (1200, 24, 0.0045, 0.5),    # landmarks (AREALM)
    "T2": (4000, 30, 0.0022, 0.5),    # water (AREAWATER)
    "T3": (64, 220, 0.085, 0.35),     # counties (COUNTY)
}

N_CLUSTERS = 16
CHUNK = 65536
#: share of R polygons placed next to an S polygon, and the width of the
#: slab between them, in map units (float32 spacing near 0.5 is 6e-8)
NEAR_SHARE = 0.01
NEAR_GAP = 1e-11
#: bound on the per-vertex displacement a run's seed draws
SEED_JITTER = 1e-14


def scaled_order(k: float) -> int:
    """Raster order that keeps cells per polygon at scale ``k``."""
    return max(6, 8 + round(math.log(k, 4)))


def _star_chunk(rng: np.random.Generator, centers: np.ndarray,
                radii: np.ndarray, nvs: np.ndarray,
                jitter: float) -> np.ndarray:
    """[n, vmax, 2] rings for one chunk; padding slots are zero."""
    n, vmax = len(nvs), int(nvs.max())
    mask = np.arange(vmax)[None, :] < nvs[:, None]
    angles = rng.uniform(0.0, 2 * np.pi, size=(n, vmax))
    angles = np.sort(np.where(mask, angles, np.inf), axis=1)
    angles = np.where(mask, angles, 0.0)
    angles += np.linspace(0, 1e-4, vmax)[None, :]   # no repeated angle
    rad = radii[:, None] * (1.0 + jitter * rng.uniform(-1.0, 1.0,
                                                       size=(n, vmax)))
    rad = np.maximum(rad, 0.15 * radii[:, None])
    pts = centers[:, None, :] + np.stack(
        [rad * np.cos(angles), rad * np.sin(angles)], axis=-1)
    pts = np.clip(pts, 1e-6, 1.0 - 1e-6)
    return np.where(mask[..., None], pts, 0.0)


def scaled(spec: str, k: float) -> dict:
    """One layer of a deployment at scale ``k``, as a configuration file
    states it: ``round(count * k)`` polygons, radii / sqrt(k)."""
    count, nv_avg, radius, jitter = SPECS[spec]
    return {"spec": spec, "count": round(count * k),
            "mean_vertices": nv_avg, "mean_radius": radius / math.sqrt(k),
            "radius_jitter": jitter}


def layer(side: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """(verts [P, Vmax, 2] float64, nverts [P] int64) of one layer as
    ``side`` (see :func:`scaled`) states it, drawn from ``seed``."""
    spec, cnt = side["spec"], int(side["count"])
    nv_avg, rad = side["mean_vertices"], side["mean_radius"]
    jitter = side["radius_jitter"]
    cl_centers = np.random.default_rng(0).uniform(0.1, 0.9,
                                                  size=(N_CLUSTERS, 2))
    verts, nverts = [], []
    for ci, start in enumerate(range(0, cnt, CHUNK)):
        m = min(CHUNK, cnt - start)
        rng = np.random.default_rng(
            zlib.crc32(f"{spec}:{seed}:chunk:{ci}".encode()))
        nvs = np.clip(rng.poisson(nv_avg, size=m), 4, None).astype(np.int64)
        radii = rad * np.exp(rng.normal(0.0, 0.45, size=m))
        spread = max(0.008, 2.5 * rad)
        cl_idx = rng.integers(0, N_CLUSTERS, size=m)
        centers = cl_centers[cl_idx] + rng.normal(0, spread, size=(m, 2))
        centers = np.clip(centers, radii[:, None] + 1e-4,
                          1.0 - radii[:, None] - 1e-4)
        verts.append(_star_chunk(rng, centers, radii, nvs, jitter))
        nverts.append(nvs)
    vmax = max(v.shape[1] for v in verts)
    verts = np.concatenate([np.pad(v, ((0, 0), (0, vmax - v.shape[1]),
                                       (0, 0))) for v in verts])
    return verts, np.concatenate(nverts)


def mbrs(verts: np.ndarray, nverts: np.ndarray) -> np.ndarray:
    """[P, 4] (xmin, ymin, xmax, ymax) over each ring's real vertices."""
    mask = np.arange(verts.shape[1])[None, :] < nverts[:, None]
    x, y = verts[..., 0], verts[..., 1]
    return np.stack([np.where(mask, x, np.inf).min(1),
                     np.where(mask, y, np.inf).min(1),
                     np.where(mask, x, -np.inf).max(1),
                     np.where(mask, y, -np.inf).max(1)], axis=1)


def near_misses(vr: np.ndarray, nr: np.ndarray, vs: np.ndarray,
                ns: np.ndarray, count: int, rng: np.random.Generator):
    """Move ``count`` R polygons, each next to an S polygon: along a
    direction d that no axis is near, R's lowest vertex along d is put
    ``NEAR_GAP`` beyond S's highest. Every R vertex then lies on the far
    side of a slab ``NEAR_GAP`` wide from every S vertex, so the pair
    does not intersect, while their MBRs overlap by at least 1e-6 on both
    axes. Returns (moved R vertices, the [count, 2] pairs placed)."""
    vr = vr.copy()
    placed: list[tuple[int, int]] = []
    used: set[int] = set()
    for _ in range(1000 * count):
        if len(placed) == count:
            break
        i, j = int(rng.integers(len(nr))), int(rng.integers(len(ns)))
        theta = np.radians(rng.uniform(20.0, 70.0) + 90.0 * rng.integers(4))
        if i in used:
            continue
        d = np.array([np.cos(theta), np.sin(theta)])
        pr, ps = vr[i, :nr[i]], vs[j, :ns[j]]
        a, v = ps[np.argmax(ps @ d)], pr[np.argmin(pr @ d)]
        moved = pr + (a + NEAR_GAP * d - v)
        lo, hi = moved.min(0), moved.max(0)
        if (lo.min() <= 1e-6 or hi.max() >= 1.0 - 1e-6
                or (np.minimum(hi, ps.max(0))
                    - np.maximum(lo, ps.min(0))).min() < 1e-6):
            continue        # off the map, or the MBRs barely meet
        vr[i, :nr[i]] = moved
        used.add(i)
        placed.append((i, j))
    if len(placed) < count:
        raise ValueError(f"placed {len(placed)} of {count} near misses")
    return vr, np.array(placed, np.int64).reshape(-1, 2)


def jitter(verts: np.ndarray, nverts: np.ndarray,
           rng: np.random.Generator) -> np.ndarray:
    """``verts`` with each real vertex moved by up to ``SEED_JITTER``
    per coordinate."""
    mask = (np.arange(verts.shape[1])[None, :] < nverts[:, None])[..., None]
    step = rng.uniform(-SEED_JITTER, SEED_JITTER, verts.shape)
    return verts + np.where(mask, step, 0.0)


def deployment(config: dict, seed: int) -> dict:
    """Both layers of a configuration: {"r": (verts, nverts, mbrs), "s":
    ..., "near": [K, 2] pairs placed by :func:`near_misses`}.

    The polygons and the near misses come from the configuration's
    ``data_seed``, so every run holds the same work; the run's ``seed``
    moves every vertex by less than ``SEED_JITTER``, far below a raster
    cell (the program's work) and the near-miss gap (the answer)."""
    k, base = float(config["k"]), int(config["data_seed"])
    layers = {side: layer(scaled(config["layers"][side]["spec"], k),
                          base + config["layers"][side]["seed_offset"])
              for side in ("r", "s")}
    (vr, nr), (vs, ns) = layers["r"], layers["s"]
    rng = np.random.default_rng(zlib.crc32(f"near:{base}".encode()))
    vr, near = near_misses(vr, nr, vs, ns,
                           max(1, round(NEAR_SHARE * len(nr))), rng)
    rng = np.random.default_rng(int(seed) % 2**64)
    vr, vs = jitter(vr, nr, rng), jitter(vs, ns, rng)
    return {"r": (vr, nr, mbrs(vr, nr)), "s": (vs, ns, mbrs(vs, ns)),
            "near": near}
