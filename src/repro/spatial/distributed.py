"""Distributed spatial-join execution (shard_map over the device mesh).

The join is partition-parallel (paper §5.2 + DESIGN.md §4), and every
pipeline stage has a mesh-sharded batched path:

* **Candidate generation** (:func:`distributed_mbr_join`, DESIGN.md §8):
  the host builds the flat co-bucket cross-product rows of the grid-hash
  MBR join; the rows shard across the mesh 'data' axis, each device
  evaluates its shard's intersection + reference-point ownership mask,
  qualifying counts psum-reduce on device, and the gathered mask emits
  the duplicate-free pair list on host.
* **Filtering** (:func:`distributed_filter`, §3/§4/§9): candidate pairs
  pack into padded, *bucketed* batches (bucketing by interval-list width
  bounds padding waste and is the primary load-balance/straggler lever)
  and dispatch with ``shard_map``; each device runs the three interval
  joins as one fused, branch-free vectorized pass. Filters that declare
  ``supports_mesh`` (APRIL) ship packed batches through the mesh kernel;
  every other registered filter runs its bucketed batched ``verdicts`` on
  the selected ``filter_backend`` — the launcher works for all of
  ``none/april/april-c/ri/ra/5cch``. Counts are psum-reduced; verdicts
  stay sharded for refinement.
* **Refinement** (:func:`distributed_refine`, §7): indecisive pairs refine
  sharded in vertex-count-bucketed chunks, guard-band-uncertain pairs
  escalating to the host, so verdicts equal the sequential oracle.

The same step functions lower on the production meshes (16x16 and 2x16x16)
— exercised by ``launch/dryrun.py --arch april_join``.

Batching contract: every entry point here is candidate-batched — it takes
``[N, 2]`` pair-index arrays (plus the padded interval/vertex operand
arrays packed from them) and dispatches whole shards; nothing loops
per pair on the host. Partitions are the outer unit of work: the
launcher (``launch/spatial_join.py``) and the §14 tiled driver
(``spatial/scaleout.py``) call these per partition, each with its own
approximations and candidate frame.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

shard_map = jax.shard_map

from ..core.join import INDECISIVE, TRUE_HIT, TRUE_NEG, pack_lists
from ..kernels import note_routed, pad_rows_pow2
from .fused import to_host

__all__ = [
    "PackedPairs", "pack_pair_batch", "bucket_pairs",
    "april_filter_kernel_jnp", "distributed_april_filter",
    "distributed_filter", "distributed_fused_join", "distributed_mbr_join",
    "distributed_refine", "make_join_mesh",
]

I32_MAX = np.int32(np.iinfo(np.int32).max)


@dataclass
class PackedPairs:
    """Padded device batch for N candidate pairs (biased-int32 inclusive)."""
    ra_s: np.ndarray; ra_l: np.ndarray; ra_n: np.ndarray   # A(r)
    rf_s: np.ndarray; rf_l: np.ndarray; rf_n: np.ndarray   # F(r)
    sa_s: np.ndarray; sa_l: np.ndarray; sa_n: np.ndarray   # A(s)
    sf_s: np.ndarray; sf_l: np.ndarray; sf_n: np.ndarray   # F(s)
    pair_idx: np.ndarray                                   # [B,2] original ids
    valid: np.ndarray                                      # [B] bool

    def __len__(self):
        return len(self.valid)

    def arrays(self) -> dict:
        return {k: getattr(self, k) for k in (
            "ra_s", "ra_l", "ra_n", "rf_s", "rf_l", "rf_n",
            "sa_s", "sa_l", "sa_n", "sf_s", "sf_l", "sf_n")}


def pack_pair_batch(store_r, store_s, pairs: np.ndarray,
                    pad_batch_to: int = 1, pad_width_to: int = 8) -> PackedPairs:
    """Pack a ``[N, 2]`` candidate-pair batch into the padded device arrays
    of :class:`PackedPairs` (DESIGN.md §9): batch padded to a multiple of
    ``pad_batch_to`` (the device count, so shards divide evenly), interval
    widths to a multiple of ``pad_width_to``. One vectorized gather per
    list kind — no per-pair host loop."""
    pairs = np.asarray(pairs, np.int64).reshape(-1, 2)
    B = len(pairs)
    Bp = max(pad_batch_to, ((B + pad_batch_to - 1) // pad_batch_to) * pad_batch_to)

    def pad_rows(x, fill):
        if len(x) == Bp:
            return x
        pad = np.full((Bp - len(x),) + x.shape[1:], fill, x.dtype)
        return np.concatenate([x, pad], axis=0)

    def mk(store, idx, kind):
        s, l, n = pack_lists(store, idx, kind, pad_to=pad_width_to)
        w = ((s.shape[1] + pad_width_to - 1) // pad_width_to) * pad_width_to
        if s.shape[1] < w:
            extra = np.full((s.shape[0], w - s.shape[1]), I32_MAX, np.int32)
            s = np.concatenate([s, extra], axis=1)
            l = np.concatenate([l, extra], axis=1)
        return pad_rows(s, I32_MAX), pad_rows(l, I32_MAX), pad_rows(n, 0)

    ra = mk(store_r, pairs[:, 0], "A")
    rf = mk(store_r, pairs[:, 0], "F")
    sa = mk(store_s, pairs[:, 1], "A")
    sf = mk(store_s, pairs[:, 1], "F")
    valid = pad_rows(np.ones(B, bool), False)
    pidx = pad_rows(pairs, -1)
    return PackedPairs(*ra, *rf, *sa, *sf, pair_idx=pidx, valid=valid)


def bucket_pairs(store_r, store_s, pairs: np.ndarray, n_devices: int = 1,
                 max_width: int = 512) -> list[PackedPairs]:
    """Split a ``[N, 2]`` pair batch into power-of-two interval-width
    buckets and pack each (DESIGN.md §9): width-bucketing bounds padding
    waste and is the primary load-balance/straggler lever of the sharded
    filter stage. Each bucket's rows pad to ``n_devices`` times a power of
    two, so the mesh step compiles once per size class, not per bucket."""
    pairs = np.asarray(pairs, np.int64).reshape(-1, 2)
    if len(pairs) == 0:
        return []
    wa = store_r.a_off[pairs[:, 0] + 1] - store_r.a_off[pairs[:, 0]]
    wb = store_s.a_off[pairs[:, 1] + 1] - store_s.a_off[pairs[:, 1]]
    width = np.maximum(np.maximum(wa, wb), 1)
    buckets: dict[int, list[int]] = {}
    for k, w in enumerate(width):
        b = 1 << int(np.ceil(np.log2(min(int(w), max_width))))
        buckets.setdefault(max(b, 8), []).append(k)
    return [
        pack_pair_batch(store_r, store_s, pairs[idx],
                        pad_batch_to=n_devices * (1 << int(np.ceil(np.log2(
                            -(-len(idx) // n_devices))))),
                        pad_width_to=bw)
        for bw, idx in sorted(buckets.items())
    ]


# ---------------------------------------------------------------------------
# Device kernel (pure jnp; the Pallas version lives in kernels/interval_join)
# ---------------------------------------------------------------------------

def _overlap_rows(xs, xl, nx, ys, yl, ny):
    """Branch-free batched interval overlap (biased-int32, inclusive-last)."""
    I = xs.shape[-1]
    idx = jax.vmap(lambda ylr, xsr: jnp.searchsorted(ylr, xsr, side="left"))(yl, xs)
    ok = idx < ny[:, None]
    jj = jnp.minimum(idx, jnp.maximum(ny - 1, 0)[:, None])
    ys_at = jnp.take_along_axis(ys, jj, axis=1)
    valid_x = jnp.arange(I, dtype=jnp.int32)[None, :] < nx[:, None]
    return jnp.any(valid_x & ok & (ys_at <= xl), axis=-1)


def april_filter_kernel_jnp(batch: dict) -> jnp.ndarray:
    """Fused AA/AF/FA filter for a packed batch -> verdicts [B] int8
    (DESIGN.md §9; the Pallas twin lives in ``kernels/interval_join``).

    All three joins are evaluated for every pair (branch-free); the verdict
    select reproduces Algorithm 2's decision tree. Batched: the input is
    the :meth:`PackedPairs.arrays` dict, one row per candidate pair.
    """
    aa = _overlap_rows(batch["ra_s"], batch["ra_l"], batch["ra_n"],
                       batch["sa_s"], batch["sa_l"], batch["sa_n"])
    af = _overlap_rows(batch["ra_s"], batch["ra_l"], batch["ra_n"],
                       batch["sf_s"], batch["sf_l"], batch["sf_n"])
    fa = _overlap_rows(batch["rf_s"], batch["rf_l"], batch["rf_n"],
                       batch["sa_s"], batch["sa_l"], batch["sa_n"])
    return jnp.where(~aa, TRUE_NEG,
                     jnp.where(af | fa, TRUE_HIT, INDECISIVE)).astype(jnp.int8)


def make_join_mesh(n_devices: int | None = None) -> Mesh:
    """1-D ``Mesh`` over the first ``n_devices`` local devices (all by
    default), axis name 'data' — the batch-sharding axis every
    ``distributed_*`` step and the §14 tiled driver shard over."""
    devs = jax.devices()
    n = n_devices or len(devs)
    return Mesh(np.asarray(devs[:n]), ("data",))


_FILTER_STEP_CACHE: dict = {}


def _filter_shard_step(mesh):
    """The sharded APRIL filter step, jitted once per mesh."""
    if mesh in _FILTER_STEP_CACHE:
        return _FILTER_STEP_CACHE[mesh]

    @partial(shard_map, mesh=mesh, in_specs=(P("data"), P("data")),
             out_specs=(P("data"), P()))
    def step(b, v):
        verd = april_filter_kernel_jnp(b)
        verd = jnp.where(v, verd, jnp.int8(-1))
        counts = jnp.stack([
            jnp.sum((verd == TRUE_NEG)), jnp.sum((verd == TRUE_HIT)),
            jnp.sum((verd == INDECISIVE))])
        counts = jax.lax.psum(counts, "data")
        return verd, counts

    _FILTER_STEP_CACHE[mesh] = jax.jit(step)
    return _FILTER_STEP_CACHE[mesh]


def distributed_april_filter(packed: PackedPairs, mesh: Mesh | None = None):
    """Run the APRIL filter kernel on one packed batch, sharded over the
    mesh 'data' axis (DESIGN.md §9).

    Returns (verdicts [B] np.int8, counts dict) — counts are psum-reduced on
    device (one scalar per verdict class crosses the network, not the batch).
    """
    mesh = mesh or make_join_mesh()
    verd, counts = _filter_shard_step(mesh)(
        {k: jnp.asarray(a) for k, a in packed.arrays().items()},
        jnp.asarray(packed.valid))
    verd, counts = to_host(verd, counts)
    return (verd,
            {"true_neg": int(counts[0]), "true_hit": int(counts[1]),
             "indecisive": int(counts[2])})


def distributed_filter(filt, approx_r, approx_s, pairs: np.ndarray,
                       mesh: Mesh | None = None, backend: str = "numpy",
                       predicate: str = "intersects",
                       filter_backend: str | None = None):
    """Filter a candidate batch through any registered intermediate filter.

    Mesh-capable filters (``filt.supports_mesh``) run sharded across the
    device mesh on the ``jnp``/``pallas`` filter backends; the rest run
    their bucketed batched ``verdicts`` on the selected backend
    (``sequential`` runs the per-pair reference loop). ``filter_backend``
    is the canonical knob name, ``backend`` its historical alias. Returns
    (verdicts [N] np.int8, counts dict).
    """
    from .filters import get_filter
    filt = get_filter(filt)
    backend = filter_backend or backend
    pairs = np.asarray(pairs, np.int64).reshape(-1, 2)
    # the mesh kernel evaluates the intersects trichotomy only; other
    # predicates run the filter's batched host path
    if (filt.supports_mesh and backend in ("jnp", "pallas")
            and predicate == "intersects"):
        return filt.verdicts_mesh(approx_r, approx_s, pairs, mesh=mesh)
    verd = filt.verdicts(approx_r, approx_s, pairs, predicate=predicate,
                         backend=backend)
    counts = {"true_neg": int(np.sum(verd == TRUE_NEG)),
              "true_hit": int(np.sum(verd == TRUE_HIT)),
              "indecisive": int(np.sum(verd == INDECISIVE))}
    return verd, counts


# ---------------------------------------------------------------------------
# Sharded candidate generation (DESIGN.md §8): bucket cross-product rows
# shard across the mesh; the gathered ownership mask emits the pair list
# ---------------------------------------------------------------------------

_MBR_STEP_CACHE: dict = {}


def _mbr_shard_step(mesh):
    if mesh in _MBR_STEP_CACHE:
        return _MBR_STEP_CACHE[mesh]
    specs = (P(), P(), P(), P()) + tuple(P("data") for _ in range(5))

    from .mbr_join import pair_mask_body

    @partial(shard_map, mesh=mesh, in_specs=specs, out_specs=(P("data"), P()))
    def step(mr, ms, lor, los, ri, si, ox, oy, v):
        keep = pair_mask_body(jnp, mr, ms, lor, los, ri, si, ox, oy) & v
        return keep, jax.lax.psum(jnp.sum(keep), "data")

    _MBR_STEP_CACHE[mesh] = jax.jit(step)
    return _MBR_STEP_CACHE[mesh]


def distributed_mbr_join(mbrs_r: np.ndarray, mbrs_s: np.ndarray,
                         grid: int | None = None, mesh: Mesh | None = None):
    """MBR candidate generation sharded over the mesh 'data' axis.

    The host runs the cheap O(N) stages of the §8 grid-hash join (bucket
    expansion, sort-merge over the bucket tables); the O(candidates)
    cross-product rows are padded to the device count and sharded, each
    device evaluates its shard's intersection + reference-point ownership
    mask against the replicated MBR/cell tables (f64 under ``enable_x64``),
    and the qualifying count psum-reduces on device. The gathered mask
    emits the pair list on host — identical to ``mbr_join`` on every
    backend. Returns (pairs [K,2] int64, counts dict).
    """
    from .mbr_join import _prepare, candidate_rows

    mbrs_r, mbrs_s, k, extent = _prepare(mbrs_r, mbrs_s, grid)
    if k == 0:
        return np.zeros((0, 2), np.int64), {"mbr_candidates": 0,
                                            "mbr_pairs": 0}
    ri, si, own_x, own_y, lo_r, lo_s = candidate_rows(mbrs_r, mbrs_s, k,
                                                      extent)
    if len(ri) == 0:
        return np.zeros((0, 2), np.int64), {"mbr_candidates": 0,
                                            "mbr_pairs": 0}
    mesh = mesh or make_join_mesh()
    n_dev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    # replicated tables pad to powers of two as well, so the shard step
    # compiles O(log) times across partition-sized inputs, not per shape
    (mbrs_r, lo_r), _ = pad_rows_pow2([mbrs_r, lo_r])
    (mbrs_s, lo_s), _ = pad_rows_pow2([mbrs_s, lo_s])
    (pri, psi, pox, poy, valid), n = pad_rows_pow2(
        [ri, si, own_x, own_y, np.ones(len(ri), bool)], multiple=n_dev)
    step = _mbr_shard_step(mesh)
    with jax.enable_x64(True):
        keep, count = step(*[jnp.asarray(a) for a in (
            mbrs_r, mbrs_s, lo_r, lo_s, pri, psi, pox, poy, valid)])
    keep_h, count_h = to_host(keep, count)
    keep_h = keep_h[:n]
    pairs = np.stack([ri[keep_h], si[keep_h]], axis=1)
    return pairs, {"mbr_candidates": int(n), "mbr_pairs": int(count_h)}


# ---------------------------------------------------------------------------
# Sharded refinement (DESIGN.md §7): the indecisive remainder stays sharded
# ---------------------------------------------------------------------------

_REFINE_STEP_CACHE: dict = {}


def _refine_shard_step(body, mesh, n_args):
    key = (body, mesh, n_args)
    if key in _REFINE_STEP_CACHE:
        return _REFINE_STEP_CACHE[key]
    specs = tuple(P("data") for _ in range(n_args)) + (P("data"),)

    @partial(shard_map, mesh=mesh, in_specs=specs,
             out_specs=(P("data"), P("data"), P()))
    def step(*xs):
        *geom, v = xs
        res, unc = body(*geom)
        res = res & v
        unc = unc & v
        return res, unc, jax.lax.psum(jnp.sum(res & ~unc), "data")

    _REFINE_STEP_CACHE[key] = jax.jit(step)
    return _REFINE_STEP_CACHE[key]


def distributed_refine(R, S, pairs: np.ndarray,
                       predicate: str = "intersects",
                       mesh: Mesh | None = None):
    """Refine indecisive candidate pairs sharded over the mesh 'data' axis.

    Pairs are split into width classes by their vertex counts rounded up
    to powers of two. A class that fills at least one chunk (``n_dev``
    times :func:`~repro.spatial.refine.device_chunk_rows` rows) gathers
    only its own vertex widths; the remaining pairs share one class at the
    datasets' full padded widths. So a typical pair's work scales with its
    own class's widths, not with the dataset's largest polygon, while every
    compiled shape beyond the full-width one is paid for by a full chunk
    of work. Each class runs in fixed-shape chunks (a power-of-two chunk
    when it is smaller, the last one padded), so the padded [rows, Er, Es]
    working set per device stays bounded. Each device runs the batched jnp
    refinement core (f64 under ``jax.enable_x64``) on its shard, and the
    count of device-decided hits is psum-reduced on device (one scalar per
    chunk crosses the network). Pairs whose sign evaluations fall inside
    the FMA guard band come back uncertain and are re-run on host, so the
    final verdicts are identical to the host backends. Returns (results
    [N] bool, counts dict).
    """
    from . import refine as refine_mod

    pairs = np.asarray(pairs, np.int64).reshape(-1, 2)
    N = len(pairs)
    if N == 0:
        return np.zeros(0, bool), {"refined_true": 0}
    mesh = mesh or make_join_mesh()
    n_dev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    intersectsish = predicate not in ("within", "linestring")
    body = (refine_mod._within_impl_jnp if predicate == "within"
            else refine_mod._line_impl_jnp if predicate == "linestring"
            else refine_mod._intersects_impl_jnp)
    step = _refine_shard_step(body, mesh, 6 if intersectsish else 4)

    def pow2(n):
        """Elementwise next power of two of counts ``n`` (at least 1)."""
        return 1 << np.ceil(np.log2(np.maximum(n, 1))).astype(np.int64)

    Wa, Wb = R.verts.shape[1], S.verts.shape[1]
    wa = np.minimum(pow2(R.nverts[pairs[:, 0]]), Wa)
    wb = np.minimum(pow2(S.nverts[pairs[:, 1]]), Wb)
    classes, inv, size = np.unique(np.stack([wa, wb], axis=1), axis=0,
                                   return_inverse=True, return_counts=True)
    full = np.array([n_dev * refine_mod.device_chunk_rows(a, b)
                     for a, b in classes.tolist()])
    small = (size < full)[inv.reshape(-1)]   # classes below one chunk
    wa[small], wb[small] = Wa, Wb
    out = np.zeros(N, bool)
    n_true = 0
    for Va, Vb in sorted(set(zip(wa.tolist(), wb.tolist()))):
        sel = np.flatnonzero((wa == Va) & (wb == Vb))
        C = n_dev * refine_mod.device_chunk_rows(Va, Vb)
        if len(sel) < C:    # a power-of-two chunk
            C = n_dev * int(pow2(-(-len(sel) // n_dev)))
        for c0 in range(0, len(sel), C):
            rows = sel[c0:c0 + C]
            p = pairs[rows]
            args = [R.verts[:, :Va][p[:, 0]], R.nverts[p[:, 0]],
                    S.verts[:, :Vb][p[:, 1]], S.nverts[p[:, 1]]]
            if intersectsish:
                args += [refine_mod._reps(R, p[:, 0]),
                         refine_mod._reps(S, p[:, 1])]
            args.append(np.ones(len(p), bool))          # valid rows
            args = [np.concatenate([a, np.zeros((C - len(p),) + a.shape[1:],
                                                a.dtype)]) for a in args]
            with jax.enable_x64(True):
                res, unc, count = step(*[jnp.asarray(a) for a in args])
            res_h, unc_h, count_h = to_host(res, unc, count)
            res_h = res_h[: len(p)].copy()
            unc_h = unc_h[: len(p)]
            n_true += int(count_h)
            note_routed(refine_mod._ESCALATED, np.count_nonzero(unc_h))
            if unc_h.any():    # guard-band pairs: exact host re-check
                res_h[unc_h] = refine_mod.refine(R, S, p[unc_h],
                                                 predicate=predicate,
                                                 backend="numpy")
                n_true += int(res_h[unc_h].sum())
            out[rows] = res_h
    return out, {"refined_true": n_true}


# ---------------------------------------------------------------------------
# Fused sharded chain (DESIGN.md §12): MBR mask + APRIL trichotomy + exact
# refinement of every shard row under ONE shard_map
# ---------------------------------------------------------------------------

_FUSED_STEP_CACHE: dict = {}


def _fused_shard_step(mesh, with_filter: bool, chunk: int):
    """The one-dispatch chain, compiled per (mesh, filter-on/off, chunk).

    ``with_filter=False`` is the per-shard plan's skip-filter variant
    (DESIGN.md §13): no interval batch enters the step — every valid row
    is INDECISIVE and refines, so tiny candidate sets avoid the packing
    and kernel work entirely while staying inside one ``shard_map``.
    Refinement walks each shard's rows ``chunk`` at a time
    (:func:`~repro.spatial.refine.map_row_chunks`), so the f64
    [rows, Er, Es] tile never materializes whole.
    """
    key = (mesh, with_filter, chunk)
    if key in _FUSED_STEP_CACHE:
        return _FUSED_STEP_CACHE[key]
    from . import refine as refine_mod
    from .mbr_join import pair_mask_body

    # replicated MBR/cell tables, then the sharded per-row operands
    specs = ((P(),) * 4
             + (P("data"),) * 5      # ri, si, own_x, own_y, valid
             + ((P("data"),) if with_filter else ())  # packed batch pytree
             + (P("data"),) * 6)     # vr, nr, rep_r, vs, ns, rep_s

    def _finish(v, verd, vr, nr, rpr, vs, ns, rps):
        res, unc = refine_mod.map_row_chunks(
            refine_mod._intersects_impl_jnp, chunk, vr, nr, vs, ns, rpr, rps)
        indec = v & (verd == INDECISIVE)
        hit = (verd == TRUE_HIT) | (indec & res)
        unc = unc & indec
        counts = jax.lax.psum(jnp.stack([
            jnp.sum(v), jnp.sum(v & (verd == TRUE_NEG)),
            jnp.sum(verd == TRUE_HIT), jnp.sum(indec)]), "data")
        return verd, hit, unc, counts

    if with_filter:
        @partial(shard_map, mesh=mesh, in_specs=specs,
                 out_specs=(P("data"), P("data"), P("data"), P()))
        def step(mr, ms, lor, los, ri, si, ox, oy, vrow, batch,
                 vr, nr, rpr, vs, ns, rps):
            v = pair_mask_body(jnp, mr, ms, lor, los, ri, si, ox, oy) & vrow
            verd = april_filter_kernel_jnp(batch)
            verd = jnp.where(v, verd, jnp.int8(TRUE_NEG))
            return _finish(v, verd, vr, nr, rpr, vs, ns, rps)
    else:
        @partial(shard_map, mesh=mesh, in_specs=specs,
                 out_specs=(P("data"), P("data"), P("data"), P()))
        def step(mr, ms, lor, los, ri, si, ox, oy, vrow,
                 vr, nr, rpr, vs, ns, rps):
            v = pair_mask_body(jnp, mr, ms, lor, los, ri, si, ox, oy) & vrow
            verd = jnp.where(v, jnp.int8(INDECISIVE), jnp.int8(TRUE_NEG))
            return _finish(v, verd, vr, nr, rpr, vs, ns, rps)

    _FUSED_STEP_CACHE[key] = jax.jit(step)
    return _FUSED_STEP_CACHE[key]


def distributed_fused_join(R, S, approx_r, approx_s,
                           grid: int | None = None, mesh: Mesh | None = None,
                           plan: "PlanChoice | None" = None):
    """The intersects join as ONE sharded dispatch (DESIGN.md §12).

    The host runs the cheap grid-hash preprocessing; every candidate row
    then flows through MBR mask -> APRIL trichotomy -> exact refinement
    inside a single ``shard_map`` step, counts psum-reduce on device, and
    the lanes come back in one :func:`~repro.spatial.fused.to_host` gather
    (plus the sanctioned f64 escalation of guard-band pairs). Refinement is
    branch-free — every shard row refines, masked by its verdict — so this
    trades redundant FLOPs for zero intermediate syncs; the staged
    ``distributed_*`` steps remain the large-batch references. Pair *set*
    (order-insensitive) equals the staged chain. APRIL stores over polygon
    sides only.

    ``plan`` carries this shard's :class:`~repro.spatial.planner.PlanChoice`
    (DESIGN.md §13): a skip-filter plan drops the interval batch from the
    step — no packing, no kernel, every valid row refines — still as one
    ``shard_map`` dispatch (``approx_r``/``approx_s`` may then be ``None``).
    The join order a plan carries is irrelevant here: the branch-free
    kernel evaluates all three joins at once. Returns
    (pairs [K,2] int64, counts dict).
    """
    from .mbr_join import _prepare, candidate_rows
    from . import refine as refine_mod

    empty = np.zeros((0, 2), np.int64)
    zero = {"mbr_pairs": 0, "true_neg": 0, "true_hit": 0, "indecisive": 0}
    mbrs_r, mbrs_s, k, extent = _prepare(R.mbrs, S.mbrs, grid)
    if k == 0:
        return empty, zero
    ri, si, own_x, own_y, lo_r, lo_s = candidate_rows(mbrs_r, mbrs_s, k,
                                                      extent)
    if len(ri) == 0:
        return empty, zero
    mesh = mesh or make_join_mesh()
    n_dev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    (mbrs_r, lo_r), _ = pad_rows_pow2([mbrs_r, lo_r])
    (mbrs_s, lo_s), _ = pad_rows_pow2([mbrs_s, lo_s])
    (pri, psi, pox, poy, vrow), n = pad_rows_pow2(
        [ri, si, own_x, own_y, np.ones(len(ri), bool)], multiple=n_dev)
    frame = np.stack([pri, psi], axis=1)
    with_filter = not (plan is not None
                       and (plan.skip_filter or plan.method == "none"))
    if with_filter:
        packed = pack_pair_batch(approx_r.store, approx_s.store,
                                 frame, pad_batch_to=n_dev)
        batch = {key: jnp.asarray(a) for key, a in packed.arrays().items()}
    vr = np.asarray(R.verts, np.float64)[pri]
    vs = np.asarray(S.verts, np.float64)[psi]
    nr = np.asarray(R.nverts, np.int32)[pri]
    ns = np.asarray(S.nverts, np.int32)[psi]
    rpr = refine_mod._reps(R, pri)
    rps = refine_mod._reps(S, psi)

    step = _fused_shard_step(mesh, with_filter, refine_mod.device_chunk_rows(
        vr.shape[1], vs.shape[1]))
    with jax.enable_x64(True):
        head = [jnp.asarray(a) for a in (mbrs_r, mbrs_s, lo_r, lo_s,
                                         pri, psi, pox, poy, vrow)]
        tail = [jnp.asarray(a) for a in (vr, nr, rpr, vs, ns, rps)]
        if with_filter:
            verd, hit, unc, counts = step(*head, batch, *tail)
        else:
            verd, hit, unc, counts = step(*head, *tail)
    verd, hit, unc, counts = to_host(verd, hit, unc, counts)
    hit, unc = hit[:n].copy(), unc[:n]
    note_routed(refine_mod._ESCALATED, np.count_nonzero(unc))
    if unc.any():          # sanctioned f64 escalation of guard-band rows
        esc = frame[:n][unc]
        hit[unc] = (verd[:n][unc] == TRUE_HIT) | refine_mod.refine(
            R, S, esc, predicate="intersects", backend="numpy")
    pairs = frame[:n][hit]
    return pairs, {"mbr_pairs": int(counts[0]), "true_neg": int(counts[1]),
                   "true_hit": int(counts[2]), "indecisive": int(counts[3])}
