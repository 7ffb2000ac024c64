"""The bucketed filter-join subsystem (DESIGN.md §9), deterministic tests.

Covers: the `filter_backend` knob threading (JoinPlan / stats / pipeline /
distributed / launcher flag), device-resident IntervalLists reuse across
calls, staged trichotomy drivers against the per-pair references on seeded
random interval lists (empty and single-interval rows included), APRIL-C's
bounded staged decode, the fused Pallas trichotomy kernel, and the
`tools/check_bench.py` CI gate. The hypothesis variants live in
``test_filter_backend_property.py``.
"""
import json
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro.core import join
from repro.core.april import AprilStore
from repro.core.join import (IntervalLists, april_trichotomy_rows,
                             within_trichotomy_rows)
from repro.core.rasterize import GLOBAL_EXTENT
from repro.datagen import make_dataset
from repro.spatial import FILTER_BACKENDS, JoinPlan

N_ORDER = 6
ROOT = pathlib.Path(__file__).resolve().parent.parent


def _random_store(rng, n_rows, p_empty=0.3, max_len=10, max_id=2**12):
    """AprilStore over random sorted disjoint lists; rows are empty with
    probability ``p_empty`` and single-interval with fair odds."""
    def lists():
        out = []
        for _ in range(n_rows):
            if rng.random() < p_empty:
                out.append(np.zeros((0, 2), np.uint64))
                continue
            n = int(rng.integers(1, max_len))
            pts = np.unique(rng.integers(0, max_id, 2 * n).astype(np.uint64))
            if len(pts) % 2:
                pts = pts[:-1]
            out.append(pts.reshape(-1, 2))
        off = np.zeros(n_rows + 1, np.int64)
        off[1:] = np.cumsum([len(l) for l in out])
        ints = (np.concatenate(out, axis=0) if any(len(l) for l in out)
                else np.zeros((0, 2), np.uint64))
        return off, ints
    a_off, a_ints = lists()
    f_off, f_ints = lists()
    return AprilStore(n_order=N_ORDER, extent=GLOBAL_EXTENT, a_off=a_off,
                      a_ints=a_ints, f_off=f_off, f_ints=f_ints)


def _all_pairs(nr, ns):
    return np.stack(np.meshgrid(np.arange(nr), np.arange(ns),
                                indexing="ij"), axis=-1).reshape(-1, 2)


@pytest.mark.parametrize("backend", ["numpy", "jnp"])
def test_trichotomy_random_lists(backend):
    """Staged trichotomy == per-pair references on random CSR lists with
    empty and single-interval rows (seeded mirror of the hypothesis test)."""
    rng = np.random.default_rng(7)
    for trial in range(6):
        sr = _random_store(rng, 5)
        ss = _random_store(rng, 6)
        pairs = _all_pairs(len(sr), len(ss))
        want = np.asarray([
            join.april_verdict_pair(sr.a_list(i), sr.f_list(i), ss.a_list(j),
                                    ss.f_list(j))
            for i, j in pairs], np.int8)
        got = april_trichotomy_rows(
            IntervalLists.from_intervals(sr.a_off, sr.a_ints),
            IntervalLists.from_intervals(sr.f_off, sr.f_ints),
            IntervalLists.from_intervals(ss.a_off, ss.a_ints),
            IntervalLists.from_intervals(ss.f_off, ss.f_ints),
            pairs[:, 0], pairs[:, 1], backend=backend)
        np.testing.assert_array_equal(got, want, err_msg=f"trial {trial}")
        want_w = np.asarray([
            join.within_verdict_pair(sr.a_list(i), sr.f_list(i),
                                     ss.a_list(j), ss.f_list(j))
            for i, j in pairs], np.int8)
        got_w = within_trichotomy_rows(
            IntervalLists.from_intervals(sr.a_off, sr.a_ints),
            IntervalLists.from_intervals(ss.a_off, ss.a_ints),
            IntervalLists.from_intervals(ss.f_off, ss.f_ints),
            pairs[:, 0], pairs[:, 1], backend=backend)
        np.testing.assert_array_equal(got_w, want_w, err_msg=f"trial {trial}")


@pytest.mark.parametrize("backend", ["numpy", "jnp", "pallas"])
def test_degenerate_order_matches_reference(backend):
    """order=("AA",) leaves AA survivors INDECISIVE (like the sequential
    reference); an order missing AA raises, like the reference."""
    rng = np.random.default_rng(11)
    sr = _random_store(rng, 4)
    ss = _random_store(rng, 4)
    pairs = _all_pairs(len(sr), len(ss))
    want = np.asarray([
        join.april_verdict_pair(sr.a_list(i), sr.f_list(i), ss.a_list(j),
                                ss.f_list(j), order=("AA",))
        for i, j in pairs], np.int8)
    got = join.april_filter_batch(sr, ss, pairs, order=("AA",),
                                  backend=backend)
    np.testing.assert_array_equal(got, want)
    with pytest.raises(ValueError, match="order must include 'AA'"):
        join.april_filter_batch(sr, ss, pairs, order=("AF", "FA"),
                                backend=backend)


def test_pallas_trichotomy_matches_reference():
    rng = np.random.default_rng(9)
    sr = _random_store(rng, 4)
    ss = _random_store(rng, 4)
    pairs = _all_pairs(len(sr), len(ss))
    want = np.asarray([
        join.april_verdict_pair(sr.a_list(i), sr.f_list(i), ss.a_list(j),
                                ss.f_list(j))
        for i, j in pairs], np.int8)
    got = join.april_filter_batch(sr, ss, pairs, backend="pallas")
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("predicate", ["intersects", "within"])
def test_pallas_wide_rows_are_counted(predicate):
    """Rows wider than the kernel tile take the host pass — verdicts
    unchanged — and every such row is counted, not hidden."""
    from repro.kernels import count_routed
    rng = np.random.default_rng(13)
    sr = _random_store(rng, 4, p_empty=0.0, max_len=400, max_id=2**16)
    ss = _random_store(rng, 4, p_empty=0.0, max_len=6, max_id=2**16)
    pairs = _all_pairs(len(sr), len(ss))
    batch = (join.april_filter_batch if predicate == "intersects"
             else join.within_filter_batch)
    want = batch(sr, ss, pairs, backend="numpy")
    widest = np.maximum(np.diff(sr.a_off)[pairs[:, 0]],
                        np.diff(ss.a_off)[pairs[:, 1]])
    if predicate == "intersects":
        widest = np.maximum.reduce([widest, np.diff(sr.f_off)[pairs[:, 0]],
                                    np.diff(ss.f_off)[pairs[:, 1]]])
    n_wide = int(np.sum(widest > join._PALLAS_MAX_WIDTH))
    assert n_wide > 0
    with count_routed() as routed:
        got = batch(sr, ss, pairs, backend="pallas")
    np.testing.assert_array_equal(got, want)
    assert routed["filter_wide_rows_host"] == n_wide
    # a JoinPlan run reports its counts in the stats
    R = make_dataset("T1", seed=3, count=30)
    S = make_dataset("T2", seed=4, count=30)
    _, st = JoinPlan(R, S, n_order=N_ORDER, filter_backend="pallas",
                     refine_backend="pallas").execute(predicate)
    assert set(st.extra["routed"]) == {"filter_wide_rows_host",
                                       "compact_long_lane_rows_jnp",
                                       "refine_escalated_rows_host"}


def test_compressed_store_bounded_decode_matches():
    """APRIL-C staged bounded decode == sequential streaming reference on
    every predicate (polygon reading), on every batched backend."""
    R = make_dataset("T1", seed=3, count=40)
    S = make_dataset("T2", seed=4, count=60)
    plan = JoinPlan(R, S, filter="april-c", n_order=N_ORDER)
    plan.build()
    for predicate in ("intersects", "within", "selection"):
        # within-containment candidates are scarce on T1xT2; verdicts are
        # defined for any pair batch, so test over the intersect candidates
        pairs = plan.candidates("intersects" if predicate == "within"
                                else predicate)
        assert len(pairs) > 5
        want = plan.filter.verdicts_seq(plan.approx_r, plan.approx_s, pairs,
                                        predicate=predicate)
        for backend in ("numpy", "jnp", "pallas"):
            got = plan.filter.verdicts(plan.approx_r, plan.approx_s, pairs,
                                       predicate=predicate, backend=backend)
            np.testing.assert_array_equal(got, want,
                                          err_msg=(predicate, backend))


# ---------------------------------------------------------------------------
# knob threading + device-store reuse
# ---------------------------------------------------------------------------

def test_filter_backend_knob_and_stats():
    R = make_dataset("T1", seed=11, count=20)
    S = make_dataset("T2", seed=12, count=30)
    ref = None
    for backend in FILTER_BACKENDS:
        plan = JoinPlan(R, S, filter="april", n_order=N_ORDER,
                        filter_backend=backend)
        res, st_ = plan.build().execute("intersects")
        assert st_.filter_backend == backend
        assert st_.backend == backend        # historical alias mirrors
        assert backend in st_.row()
        if ref is None:
            ref = np.sort(res, axis=0)
        else:
            np.testing.assert_array_equal(np.sort(res, axis=0), ref)


def test_filter_backend_alias_and_validation():
    R = make_dataset("T1", seed=11, count=5)
    S = make_dataset("T2", seed=12, count=5)
    with pytest.warns(DeprecationWarning, match="deprecated alias"):
        plan = JoinPlan(R, S, filter="none", backend="jnp")
    assert plan.filter_backend == "jnp"
    assert plan.backend == "jnp"
    with pytest.raises(ValueError, match="not both"):
        JoinPlan(R, S, filter="none", filter_backend="numpy", backend="jnp")
    with pytest.raises(ValueError, match="unknown filter backend"):
        JoinPlan(R, S, filter="none", filter_backend="cuda")


def test_pipeline_shim_threads_filter_backend():
    from repro.spatial.pipeline import spatial_intersection_join
    R = make_dataset("T1", seed=17, count=15)
    S = make_dataset("T2", seed=18, count=20)
    res_a, st_a = spatial_intersection_join(R, S, method="april",
                                            n_order=N_ORDER,
                                            filter_backend="sequential")
    assert st_a.filter_backend == "sequential"
    res_b, st_b = spatial_intersection_join(R, S, method="april",
                                            n_order=N_ORDER)
    np.testing.assert_array_equal(np.sort(res_a, axis=0),
                                  np.sort(res_b, axis=0))


def test_interval_lists_cached_across_calls():
    """The device-ready lists build once per Approximation and are reused
    across verdicts calls (DESIGN.md §9 device-store reuse)."""
    R = make_dataset("T1", seed=13, count=20)
    S = make_dataset("T2", seed=14, count=30)
    plan = JoinPlan(R, S, filter="april", n_order=N_ORDER)
    plan.build()
    pairs = plan.candidates("intersects")
    plan.filter.verdicts(plan.approx_r, plan.approx_s, pairs)
    cached = plan.approx_r.meta["interval_lists"]["A"]
    assert isinstance(cached, IntervalLists)
    plan.filter.verdicts(plan.approx_r, plan.approx_s, pairs)
    assert plan.approx_r.meta["interval_lists"]["A"] is cached


def test_distributed_filter_backend_alias():
    from repro.spatial.distributed import distributed_filter
    R = make_dataset("T1", seed=15, count=10)
    S = make_dataset("T2", seed=16, count=12)
    plan = JoinPlan(R, S, filter="ri", n_order=N_ORDER)
    plan.build()
    pairs = plan.candidates("intersects")
    v1, c1 = distributed_filter("ri", plan.approx_r, plan.approx_s, pairs,
                                filter_backend="numpy")
    v2, c2 = distributed_filter("ri", plan.approx_r, plan.approx_s, pairs,
                                backend="sequential")
    np.testing.assert_array_equal(v1, v2)
    assert c1 == c2


def test_launcher_exposes_filter_backend_flag():
    src = (ROOT / "src" / "repro" / "launch" / "spatial_join.py").read_text()
    assert '"--filter-backend"' in src


# ---------------------------------------------------------------------------
# the check_bench CI gate
# ---------------------------------------------------------------------------

def _run_gate(*paths):
    return subprocess.run(
        [sys.executable, str(ROOT / "tools" / "check_bench.py"),
         *map(str, paths)], capture_output=True, text=True)


def test_check_bench_gate_committed_artifacts_green():
    r = _run_gate()
    assert r.returncode == 0, r.stdout + r.stderr


def test_check_bench_gate_rejects_regressions(tmp_path):
    ok = tmp_path / "BENCH_ok.json"
    ok.write_text(json.dumps(
        {"methods": {"m": {"speedup": 2.0, "verdicts_equal": True}}}))
    assert _run_gate(ok).returncode == 0
    for bad in ({"methods": {"m": {"speedup": 0.4, "verdicts_equal": True}}},
                {"methods": {"m": {"speedup": 3.0, "verdicts_equal": False}}},
                {"methods": {"m": {"pair_sets_equal": False, "speedup": 2.0}}},
                {"no": "speedup at all"}):
        p = tmp_path / "BENCH_bad.json"
        p.write_text(json.dumps(bad))
        assert _run_gate(p).returncode == 1, bad
    p = tmp_path / "BENCH_trunc.json"
    p.write_text('{"methods": ')
    assert _run_gate(p).returncode == 1
