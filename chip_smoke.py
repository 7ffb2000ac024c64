#!/usr/bin/env python3
"""Smoke run of the spatial-join device path on a TPU.

Drives the join once through the entry points a user calls — ``JoinPlan``,
``JoinService``, and (``--chips 4``) the launcher's ``run_join`` — on
seeded T1 x T2 workloads, and checks every phase's result pair set against
the host ``numpy``/``staged`` ``JoinPlan`` of the same data. Any mismatch,
error or request timeout fails the run.

    python chip_smoke.py              # phases (a)-(d) on one chip
    python chip_smoke.py --chips 4    # launcher mesh paths: 4 devices vs 1

Phases on one chip (T1 x T2 at ``K`` = 10 times the spec table's counts,
radii divided by sqrt(K) so each polygon's candidate density holds):

  (a) APRIL, mbr jnp, filter pallas, refine pallas — intersects and within
      (the trichotomy, overlap and refine kernels);
  (b) the same with ``pipeline_mode="fused"`` (the compaction kernel, the
      device status lanes and the f64 fused refinement);
  (c) RI with the pallas filter (the ri_and kernel);
  (d) a ``JoinService`` with the (b) backends answering queries against the
      registered T2, each ticket under a timeout.

Each phase prints one line: candidates, TRUE_HIT / TRUE_NEG / INDECISIVE,
the rows routed off the device path (interval rows too wide for the kernel
tile, plus guard-band escalations to host f64) and its wall seconds. The
wall seconds are smoke timings — compilation included, one run — not
benchmark results. The last line is one JSON object naming the device.

The script never picks a platform: it exits non-zero, printing no result,
unless JAX's first device is a TPU. It runs in one process.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

#: one-chip workload scale (see ``workload``) and query count
K = 10
SERVICE_QUERIES = 24
TICKET_TIMEOUT_S = 300.0
#: ``--chips 4`` launcher workload, one partition: T1 x T2 at 2.5x the
#: spec counts and unscaled radii (about 0.5M candidate pairs, 6x the
#: spec table's 80,892)
MESH_COUNTS = (3000, 10000)


def log(msg: str) -> None:
    print(msg, flush=True)


def cache_entries(path: str) -> int:
    """Entries in a compile-cache directory (0 if it does not exist)."""
    p = Path(path)
    return sum(1 for _ in p.iterdir()) if p.is_dir() else 0


# ---------------------------------------------------------------------------
# Workload and reference
# ---------------------------------------------------------------------------

def workload(k: float, seed: int = 0):
    """T1 x T2 at ``k`` times the spec counts, radii / sqrt(k), and the
    raster order that keeps cells per polygon: 10 at k=10, 11 at k=40."""
    from repro.datagen.synthetic import DATASET_SPECS, make_chunked_dataset

    def side(name, s):
        count, _, radius, _ = DATASET_SPECS[name]
        return make_chunked_dataset(name, seed=s, count=round(count * k),
                                    avg_radius=radius / math.sqrt(k))

    n_order = max(6, 8 + round(math.log(k, 4)))
    return side("T1", seed), side("T2", seed + 1), n_order


def pair_set(pairs):
    """Canonical form of a result: unique rows in lexicographic order."""
    import numpy as np
    pairs = np.asarray(pairs, np.int64).reshape(-1, 2)
    return np.unique(pairs, axis=0)


def check_equal(label: str, got, want) -> None:
    import numpy as np
    g, w = pair_set(got), pair_set(want)
    if len(g) != len(np.asarray(got).reshape(-1, 2)):
        raise AssertionError(f"{label}: duplicate result pairs")
    if not np.array_equal(g, w):
        raise AssertionError(f"{label}: pair set differs from the host "
                             f"reference ({len(g)} vs {len(w)} pairs)")


def host_reference(R, S, n_order: int, method: str, predicates):
    """Host numpy/staged JoinPlan: ({predicate: pairs}, plan). The plan's
    approximations are reused by the device phases of the same method."""
    from repro.spatial import JoinPlan
    plan = JoinPlan(R, S, filter=method, n_order=n_order).build()
    return {p: plan.execute(p)[0] for p in predicates}, plan


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def _routed_line(routed: dict) -> str:
    wide = routed.get("filter_wide_rows_host", 0)
    esc = routed.get("refine_escalated_rows_host", 0)
    lane = routed.get("compact_long_lane_rows_jnp", 0)
    return (f"host_rows={wide + esc} (wide={wide} escalated={esc}) "
            f"jnp_lane_rows={lane}")


def _stages(st) -> str:
    """Host-clock stage seconds (fused: dispatch only, device work lands
    in t_sync)."""
    t = st.stage_times()
    return "/".join(f"{k[2:]}:{t[k]}" for k in
                    ("t_mbr", "t_filter", "t_refine", "t_sync"))


def run_plan_phase(name: str, R, S, n_order: int, ref: dict, prebuilt,
                   **knobs) -> list[str]:
    """One JoinPlan configuration per predicate of ``ref``, checked against
    the host reference; returns the printed lines."""
    from repro.spatial import JoinPlan
    lines = []
    for predicate, want in ref.items():
        t0 = time.perf_counter()
        plan = JoinPlan(R, S, n_order=n_order, **knobs).build(prebuilt)
        got, st = plan.execute(predicate)
        wall = time.perf_counter() - t0
        check_equal(f"phase {name} {predicate}", got, want)
        line = (f"phase {name} {predicate}: candidates={st.n_candidates} "
                f"TRUE_HIT={st.n_true_hits} TRUE_NEG={st.n_true_negs} "
                f"INDECISIVE={st.n_indecisive} results={len(got)} "
                f"{_routed_line(st.extra['routed'])} "
                f"stage_s={_stages(st)} "
                f"wall_s={wall} (smoke timing, compile included)")
        log(line)
        lines.append(line)
    return lines


DEVICE_KNOBS = dict(mbr_backend="jnp", filter_backend="pallas",
                    refine_backend="pallas")


def phase_a(R, S, n_order, ref, prebuilt):
    return run_plan_phase("a", R, S, n_order, ref, prebuilt,
                          filter="april", **DEVICE_KNOBS)


def phase_b(R, S, n_order, ref, prebuilt):
    return run_plan_phase("b", R, S, n_order, ref, prebuilt,
                          filter="april", pipeline_mode="fused",
                          **DEVICE_KNOBS)


def phase_c(R, S, n_order, ref):
    """RI through the ri_and kernel (intersects; RI builds its own stores)."""
    return run_plan_phase("c", R, S, n_order,
                          {"intersects": ref["intersects"]}, None,
                          filter="ri", **DEVICE_KNOBS)


def phase_d(R, S, n_order, ref_intersects, n_queries: int = SERVICE_QUERIES,
            timeout_s: float = TICKET_TIMEOUT_S, seed: int = 0):
    """JoinService over the registered T2 with the fused device backends:
    T1 polygons as intersects/selection queries; each answer must equal the
    reference pairs of that T1 object."""
    import numpy as np
    from repro.kernels import ROUTED_KEYS
    from repro.spatial import JoinService

    rng = np.random.default_rng(seed)
    qids = rng.choice(len(R), size=min(n_queries, len(R)), replace=False)
    t0 = time.perf_counter()
    svc = JoinService(method="april", n_order=n_order,
                      pipeline_mode="fused", **DEVICE_KNOBS)
    svc.register_dataset("T2", S)
    svc.start()
    try:
        tickets = [svc.submit("T2", ("intersects", "selection")[i % 2],
                              R.polygon(int(q))) for i, q in enumerate(qids)]
        for t in tickets:
            t.wait(timeout=timeout_s)
    finally:
        svc.stop()
    wall = time.perf_counter() - t0
    ref = np.asarray(ref_intersects, np.int64).reshape(-1, 2)
    n_results = 0
    for q, t in zip(qids, tickets):
        want = ref[ref[:, 0] == q][:, 1]
        got = t.pairs[:, 0]
        n_results += len(got)
        if not np.array_equal(np.sort(got), np.sort(want)):
            raise AssertionError(f"phase d: query T1[{q}] answered "
                                 f"{len(got)} pairs, reference {len(want)}")
    lat = svc.latency_stats()
    # every request of a micro-batch shares its group's stats envelope
    groups = {id(t.stats): t.stats["extra"]["routed"] for t in tickets}
    routed = {k: sum(g[k] for g in groups.values()) for k in ROUTED_KEYS}
    line = (f"phase d service: queries={len(tickets)} "
            f"batches={svc.stats['batches']} results={n_results} "
            f"{_routed_line(routed)} p50_s={lat['p50_s']} "
            f"p99_s={lat['p99_s']} wall_s={wall} "
            "(smoke timing, compile and T2 store build included)")
    log(line)
    return [line]


def run_one_chip(k: float) -> None:
    t0 = time.perf_counter()
    R, S, n_order = workload(k)
    log(f"workload: k={k} T1={len(R)} T2={len(S)} n_order={n_order} "
        f"geometry_mib={(R.verts.nbytes + S.verts.nbytes) / 2**20:.1f} "
        f"gen_s={time.perf_counter() - t0}")
    t0 = time.perf_counter()
    ref, ref_plan = host_reference(R, S, n_order, "april",
                                   ("intersects", "within"))
    log(f"host reference: intersects={len(ref['intersects'])} "
        f"within={len(ref['within'])} pairs, wall_s="
        f"{time.perf_counter() - t0} (host numpy, build included)")
    prebuilt = (ref_plan.approx_r, ref_plan.approx_s)
    phase_a(R, S, n_order, ref, prebuilt)
    phase_b(R, S, n_order, ref, prebuilt)
    phase_c(R, S, n_order, ref)
    phase_d(R, S, n_order, ref["intersects"])


def run_mesh(n_devices: int, counts=MESH_COUNTS) -> list[str]:
    """The launcher's mesh paths (fused, and staged mbr/filter/refine on
    jnp) on ``n_devices`` against one device: identical pair sets."""
    from repro.launch.spatial_join import run_join
    from repro.spatial.distributed import make_join_mesh

    count_r, count_s = counts
    lines = []
    configs = {"fused": dict(pipeline_mode="fused"),
               "staged-jnp": dict(backend="jnp", mbr_backend="jnp",
                                  refine_backend="jnp")}
    for name, knobs in configs.items():
        got = {}
        for n in (n_devices, 1):
            t0 = time.perf_counter()
            got[n], totals = run_join("T1", "T2", n_order=8, parts=1,
                                      count_r=count_r, count_s=count_s,
                                      mesh=make_join_mesh(n), **knobs)
            wall = time.perf_counter() - t0
            routed = totals.pop("routed")
            lines.append(f"mesh {name}: devices={n} results={len(got[n])} "
                         f"counts={totals} {_routed_line(routed)} "
                         f"wall_s={wall} (smoke timing, compile included)")
            log(lines[-1])
        if n_devices != 1:
            check_equal(f"mesh {name}: {n_devices} devices against 1",
                        got[n_devices], got[1])
    return lines


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the launcher's mesh paths on four "
                         "devices against one")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke.py: the repository's src/repro is not next to "
              "this script", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py: needs a TPU, JAX's first device is "
              f"{dev.platform!r}", file=sys.stderr)
        return 3
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke.py: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(jax.devices())}", file=sys.stderr)
        return 3

    from repro.runtime.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    entries0 = cache_entries(cache_dir)
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {cache_dir} entries_before={entries0}")

    t0 = time.perf_counter()
    if args.chips == 4:
        run_mesh(4)
    else:
        run_one_chip(K)
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"compile cache {cache_dir} entries_before={entries0} "
        f"entries_after={cache_entries(cache_dir)} "
        f"total_wall_s={time.perf_counter() - t0}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
