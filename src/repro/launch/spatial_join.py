"""Distributed spatial-join launcher — the paper's system as a service run.

  PYTHONPATH=src python -m repro.launch.spatial_join --r T1 --s T2 \
      --n-order 8 --parts 2 --method ri --filter-backend numpy \
      --ckpt-dir /tmp/join_ckpt

Orchestration (DESIGN.md §4): partition the map (§5.2) -> per-partition
approximations through the `IntermediateFilter` registry (any of
none/april/april-c/ri/ra/5cch) -> MBR join per partition -> batched filter
verdicts, mesh-sharded for mesh-capable filters (APRIL) or host-batched for
the rest -> batched refinement of the indecisive remainder. Fault tolerance:
per-partition results checkpoint through CheckpointManager, so a killed run
resumes at partition granularity; the WorkQueue re-leases partitions whose
workers stall (straggler mitigation).

``--plan-mode adaptive`` plans per partition (DESIGN.md §13), sharing
planner choices between partitions of similar candidate density through a
:class:`~repro.spatial.planner.ProfileCache` — only the first partition of
each density bucket pays for sampling.

``--tile-budget BYTES`` switches to the out-of-core tiled driver
(DESIGN.md §14, "Scaling beyond one device" in README.md): datasets stream
in as generated chunks, the cost-balanced partitioner packs them into
memory-budgeted tiles (``--balance static`` keeps the uniform grid), and
every finished tile checkpoints to ``--ckpt-dir`` — rerun with ``--resume``
to continue a killed run at the first unfinished tile.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from ..core import partition as partition_mod
from ..core.join import INDECISIVE, TRUE_HIT
from ..datagen import PolygonDataset, make_dataset
from ..kernels import count_routed
from ..runtime.checkpoint import CheckpointManager
from ..runtime.compile_cache import enable_compile_cache
from ..runtime.elastic import WorkQueue
from ..spatial import refine
from ..spatial.distributed import (distributed_filter, distributed_fused_join,
                                   distributed_mbr_join, distributed_refine,
                                   make_join_mesh)
from ..spatial.filters import get_filter
from ..spatial.fused import check_pipeline_mode
from ..spatial.mbr_join import mbr_join
from ..spatial.plan import JoinPlan
from ..spatial.planner import ProfileCache, check_plan_mode


def join_partition(R, S, approx_r, approx_s, parting, pidx, mesh, filt,
                   backend: str = "jnp", refine_backend: str = "numpy",
                   mbr_backend: str = "numpy", pipeline_mode: str = "staged",
                   plan_mode: str = "static", n_order: int = 8,
                   profile_cache=None):
    """Filter + refine all candidate pairs owned by partition ``pidx``.

    ``mbr_backend='jnp'`` generates the partition's candidates sharded over
    the mesh (DESIGN.md §8, bucket cross-product rows sharded, pair lists
    gathered); other values run the host grid-hash join.
    ``refine_backend='jnp'`` refines the indecisive remainder sharded over
    the mesh (verdicts stay sharded end-to-end, DESIGN.md §7); other
    backends run the batched host refinement.
    ``pipeline_mode='fused'`` (APRIL only) runs the partition's whole
    MBR -> filter -> refine chain as one sharded dispatch
    (:func:`~repro.spatial.distributed.distributed_fused_join`) with the
    cross-partition ownership dedup applied to the joined pairs — the
    result set is identical to the staged chain; per-partition counts
    then cover the partition's full candidate frame.

    ``plan_mode='adaptive'`` (DESIGN.md §13) gives each partition its own
    plan: the sample-based planner runs on the partition's candidates, and
    an april/none choice executes under ONE ``shard_map`` step via
    :func:`~repro.spatial.distributed.distributed_fused_join` with the
    per-shard plan (skip-filter plans drop the interval kernel entirely);
    other choices run the partition's batched host path. Prebuilt
    partition stores are reused when the choice matches their
    method/granularity, rebuilt locally otherwise. A ``profile_cache``
    (:class:`~repro.spatial.planner.ProfileCache`) shares planner choices
    between partitions of similar candidate density — a cache hit adopts
    the cached :class:`~repro.spatial.planner.PlanChoice` instead of
    re-sampling this partition."""
    part = parting.partitions[pidx]
    ridx = part.obj_idx[R.name]
    sidx = part.obj_idx[S.name]
    ar, as_ = approx_r[pidx], approx_s[pidx]
    if len(ridx) == 0 or len(sidx) == 0:
        return np.zeros((0, 2), np.int64), {}

    if plan_mode == "adaptive":
        Rp = PolygonDataset(name=R.name, verts=R.verts[ridx],
                            nverts=R.nverts[ridx])
        Sp = PolygonDataset(name=S.name, verts=S.verts[sidx],
                            nverts=S.nverts[sidx])
        probe = JoinPlan(Rp, Sp, filter="april", n_order=n_order,
                         refine_backend=refine_backend
                         if refine_backend != "jnp" else "numpy",
                         plan_mode="adaptive")
        choice = key = None
        if profile_cache is not None:
            cand = probe.candidates("intersects")
            key = profile_cache.key("intersects", len(Rp), len(Sp),
                                    len(cand))
            choice = profile_cache.get(key)
            if choice is not None:
                probe._apply_choice(choice)
            else:
                choice = probe.plan("intersects", pairs=cand)
                profile_cache.put(key, choice)
        else:
            choice = probe.plan("intersects")
        if choice.method in ("april", "none"):
            if choice.skip_filter:
                ar2 = as2 = None
            elif (filt.name == "april" and choice.n_order == n_order
                    and ar is not None and as_ is not None):
                ar2, as2 = ar, as_
            else:
                april = get_filter("april")
                ar2 = april.build(Rp, n_order=choice.n_order, side="r")
                as2 = april.build(Sp, n_order=choice.n_order, side="s")
            local_pairs, counts = distributed_fused_join(
                Rp, Sp, ar2, as2, mesh=mesh, plan=choice)
        else:
            local_pairs, st = probe.execute("intersects")
            counts = {"true_neg": st.n_true_negs,
                      "true_hit": st.n_true_hits,
                      "indecisive": st.n_indecisive}
        counts = dict(counts)
        counts["plan"] = choice.key()
        if len(local_pairs) == 0:
            return np.zeros((0, 2), np.int64), counts
        own = partition_mod.reference_partitions(
            parting.parts_per_dim, R.mbrs[ridx[local_pairs[:, 0]]],
            S.mbrs[sidx[local_pairs[:, 1]]]) == pidx
        local_pairs = local_pairs[own]
        out = np.stack([ridx[local_pairs[:, 0]], sidx[local_pairs[:, 1]]],
                       axis=1)
        return out, counts

    if filt.name != "none" and (ar is None or as_ is None):
        return np.zeros((0, 2), np.int64), {}

    if pipeline_mode == "fused":
        if filt.name != "april":
            raise ValueError("pipeline_mode='fused' in the distributed "
                             "launcher needs --method april (the sharded "
                             f"fused chain), got {filt.name!r}")
        Rp = PolygonDataset(name=R.name, verts=R.verts[ridx],
                            nverts=R.nverts[ridx])
        Sp = PolygonDataset(name=S.name, verts=S.verts[sidx],
                            nverts=S.nverts[sidx])
        local_pairs, counts = distributed_fused_join(Rp, Sp, ar, as_,
                                                     mesh=mesh)
        if len(local_pairs) == 0:
            return np.zeros((0, 2), np.int64), counts
        own = partition_mod.reference_partitions(
            parting.parts_per_dim, R.mbrs[ridx[local_pairs[:, 0]]],
            S.mbrs[sidx[local_pairs[:, 1]]]) == pidx
        local_pairs = local_pairs[own]
        out = np.stack([ridx[local_pairs[:, 0]], sidx[local_pairs[:, 1]]],
                       axis=1)
        return out, counts

    if mbr_backend == "jnp":
        local_pairs, _ = distributed_mbr_join(R.mbrs[ridx], S.mbrs[sidx],
                                              mesh=mesh)
    else:
        local_pairs = mbr_join(R.mbrs[ridx], S.mbrs[sidx],
                               backend=mbr_backend)
    if len(local_pairs) == 0:
        return np.zeros((0, 2), np.int64), {}
    # ownership: reference point must fall inside this partition's tile
    own = partition_mod.reference_partitions(
        parting.parts_per_dim, R.mbrs[ridx[local_pairs[:, 0]]],
        S.mbrs[sidx[local_pairs[:, 1]]]) == pidx
    local_pairs = local_pairs[own]
    if len(local_pairs) == 0:
        return np.zeros((0, 2), np.int64), {}

    verd, counts = distributed_filter(filt, ar, as_, local_pairs, mesh=mesh,
                                      backend=backend)
    results = []
    hits = local_pairs[verd == TRUE_HIT]
    indec = local_pairs[verd == INDECISIVE]
    if len(indec):
        glob = np.stack([ridx[indec[:, 0]], sidx[indec[:, 1]]], axis=1)
        if refine_backend == "jnp":
            ref, rcounts = distributed_refine(R, S, glob, mesh=mesh)
            counts = {**counts, **rcounts}
        else:
            ref = refine.refine_pairs(R, S, glob, backend=refine_backend)
            counts = {**counts, "refined_true": int(ref.sum())}
        results.append(glob[ref])
    if len(hits):
        results.append(np.stack([ridx[hits[:, 0]], sidx[hits[:, 1]]], axis=1))
    out = (np.concatenate(results, axis=0) if results
           else np.zeros((0, 2), np.int64))
    return out, counts


def run_join(r_name="T1", s_name="T2", n_order=8, parts=2, ckpt_dir=None,
             seed=0, count_r=None, count_s=None, mesh=None, method="april",
             backend="jnp", refine_backend="numpy", mbr_backend="numpy",
             build_backend="numpy", pipeline_mode="staged",
             plan_mode="static"):
    check_pipeline_mode(pipeline_mode)
    check_plan_mode(plan_mode)
    filt = get_filter(method)
    R = make_dataset(r_name, seed=seed, count=count_r)
    S = make_dataset(s_name, seed=seed + 1, count=count_s)
    mesh = mesh or make_join_mesh()
    profile_cache = ProfileCache() if plan_mode == "adaptive" else None

    t0 = time.perf_counter()
    parting = partition_mod.partition_space([R, S], parts_per_dim=parts)
    if plan_mode == "adaptive":
        # no global prebuild: every partition's planner decides its own
        # method/granularity and builds (or skips) stores locally
        approx_r = [None] * len(parting)
        approx_s = [None] * len(parting)
    else:
        approx_r = parting.build_approx(filt, R, n_order, side="r",
                                        build_backend=build_backend)
        approx_s = parting.build_approx(filt, S, n_order, side="s",
                                        build_backend=build_backend)
    t_build = time.perf_counter() - t0

    mgr = CheckpointManager(ckpt_dir, keep=2) if ckpt_dir else None
    done: dict[int, np.ndarray] = {}
    if mgr is not None:
        restored = mgr.restore()
        if restored is not None:
            _, flat, extra = restored
            done = {int(k.split("_")[1]): v for k, v in flat.items()
                    if k.startswith("part_")}
            print(f"[resume] {len(done)} partitions already joined")

    queue = WorkQueue([p for p in range(len(parting)) if p not in done],
                      lease_seconds=600)
    totals = {"true_neg": 0, "true_hit": 0, "indecisive": 0,
              "refined_true": 0}
    t0 = time.perf_counter()
    with count_routed() as routed:
        while not queue.finished:
            p = queue.acquire()
            if p is None:
                break
            res, counts = join_partition(R, S, approx_r, approx_s, parting,
                                         p, mesh, filt, backend=backend,
                                         refine_backend=refine_backend,
                                         mbr_backend=mbr_backend,
                                         pipeline_mode=pipeline_mode,
                                         plan_mode=plan_mode,
                                         n_order=n_order,
                                         profile_cache=profile_cache)
            done[p] = res
            for k in totals:
                totals[k] += counts.get(k, 0)
            queue.complete(p)
            if mgr is not None:
                mgr.save(len(done),
                         {f"part_{k}": v for k, v in done.items()})
    t_join = time.perf_counter() - t0
    totals["routed"] = routed
    if mgr is not None:
        mgr.wait()

    results = np.concatenate([v for v in done.values() if len(v)], axis=0) \
        if any(len(v) for v in done.values()) else np.zeros((0, 2), np.int64)
    cache_note = (f"  plan cache {profile_cache.stats}"
                  if profile_cache is not None else "")
    print(f"build {t_build:.2f}s  join {t_join:.2f}s  "
          f"results {len(results)}  filter counts {totals}{cache_note}")
    return results, totals


def run_tiled_join(r_name="T1", s_name="T2", *, tile_budget: int,
                   n_order=8, balance="cost", ckpt_dir=None, resume=True,
                   seed=0, count_r=None, count_s=None, chunk_size=65536,
                   mesh=None, method="april", backend="numpy",
                   refine_backend="numpy", mbr_backend="numpy",
                   pipeline_mode="staged", plan_mode="static"):
    """Out-of-core tiled scale-out run (DESIGN.md §14): both datasets
    stream in as generated chunks (never materialized whole), the
    cost-balanced partitioner packs them into ``tile_budget``-byte tiles,
    and :func:`~repro.spatial.scaleout.tiled_join` drives the per-tile
    joins — checkpointing every finished tile to ``ckpt_dir`` so a rerun
    with ``resume=True`` continues at the first unfinished tile. The
    summary line surfaces the §14 stats additions (``tiles``,
    ``t_partition``) next to the per-stage times."""
    from ..datagen import iter_dataset_chunks
    from ..spatial.planner import ProfileCache
    from ..spatial.scaleout import tiled_join

    check_pipeline_mode(pipeline_mode)
    check_plan_mode(plan_mode)
    profile_cache = ProfileCache() if plan_mode == "adaptive" else None
    with count_routed() as routed:
        pairs, stats = tiled_join(
            iter_dataset_chunks(r_name, seed=seed, count=count_r,
                                chunk_size=chunk_size),
            iter_dataset_chunks(s_name, seed=seed + 1, count=count_s,
                                chunk_size=chunk_size),
            method=method, n_order=n_order, filter_backend=backend,
            refine_backend=refine_backend, mbr_backend=mbr_backend,
            pipeline_mode=pipeline_mode, plan_mode=plan_mode, mesh=mesh,
            ckpt_dir=ckpt_dir, resume=resume, profile_cache=profile_cache,
            tile_budget=tile_budget, balance=balance, seed=seed)
    stats.extra["routed"] = routed
    resumed = stats.extra.get("resumed_tiles", 0)
    print(f"tiles {stats.tiles} ({resumed} resumed)  "
          f"partition {stats.t_partition:.2f}s  build {stats.t_build:.2f}s  "
          f"results {len(pairs)}  routed rows {routed}")
    print(stats.row())
    return pairs, stats


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--r", default="T1")
    ap.add_argument("--s", default="T2")
    ap.add_argument("--n-order", type=int, default=8)
    ap.add_argument("--parts", type=int, default=2)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--count-r", type=int, default=None)
    ap.add_argument("--count-s", type=int, default=None)
    ap.add_argument("--method", default="april",
                    help="intermediate filter: none/april/april-c/ri/ra/5cch")
    ap.add_argument("--filter-backend", default=None,
                    help="filter_backend: numpy/jnp/pallas/sequential "
                         "(jnp/pallas run mesh-capable filters sharded "
                         "over the mesh; default: --backend)")
    ap.add_argument("--backend", default="jnp",
                    help="historical alias of --filter-backend")
    ap.add_argument("--refine-backend", default="numpy",
                    help="refinement backend: numpy/jnp/pallas/sequential "
                         "(jnp refines sharded over the mesh)")
    ap.add_argument("--mbr-backend", default="numpy",
                    help="candidate-generation backend: numpy/jnp/sequential "
                         "(jnp generates candidates sharded over the mesh)")
    ap.add_argument("--build-backend", default="numpy",
                    help="store-build backend: numpy/jnp (threaded to every "
                         "per-partition filter build via build_opts)")
    ap.add_argument("--pipeline-mode", default="staged",
                    help="staged (host stage boundaries, default) or fused "
                         "(whole partition chain as one sharded dispatch, "
                         "DESIGN.md §12; APRIL only)")
    ap.add_argument("--plan-mode", default="static",
                    help="static (use the knobs above verbatim, default) or "
                         "adaptive (per-partition sample-based planner "
                         "picks method/granularity/order, DESIGN.md §13)")
    ap.add_argument("--tile-budget", type=int, default=None,
                    help="resident bytes per tile; switches to the "
                         "out-of-core tiled driver (DESIGN.md §14): "
                         "datasets stream in chunked, partitions pack into "
                         "memory-budgeted tiles, finished tiles checkpoint "
                         "to --ckpt-dir")
    ap.add_argument("--balance", default="cost",
                    help="tiled driver only: 'cost' (skew-split + "
                         "cost-balanced packing, default) or 'static' "
                         "(uniform grid, partition-order packing)")
    ap.add_argument("--resume", action="store_true",
                    help="tiled driver only: resume from the --ckpt-dir "
                         "completed-tile manifest (skips straight to the "
                         "first unfinished tile; a changed workload or "
                         "config starts fresh)")
    ap.add_argument("--chunk-size", type=int, default=65536,
                    help="tiled driver only: generated objects per "
                         "streamed chunk")
    args = ap.parse_args()
    enable_compile_cache()
    if args.tile_budget is not None:
        run_tiled_join(args.r, args.s, tile_budget=args.tile_budget,
                       n_order=args.n_order, balance=args.balance,
                       ckpt_dir=args.ckpt_dir, resume=args.resume,
                       count_r=args.count_r, count_s=args.count_s,
                       chunk_size=args.chunk_size, method=args.method,
                       backend=args.filter_backend or "numpy",
                       refine_backend=args.refine_backend,
                       mbr_backend=args.mbr_backend,
                       pipeline_mode=args.pipeline_mode,
                       plan_mode=args.plan_mode)
        return
    run_join(args.r, args.s, n_order=args.n_order, parts=args.parts,
             ckpt_dir=args.ckpt_dir, count_r=args.count_r,
             count_s=args.count_s, method=args.method,
             backend=args.filter_backend or args.backend,
             refine_backend=args.refine_backend,
             mbr_backend=args.mbr_backend,
             build_backend=args.build_backend,
             pipeline_mode=args.pipeline_mode, plan_mode=args.plan_mode)


if __name__ == "__main__":
    main()
