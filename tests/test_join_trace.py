"""The trace block of ``JoinPlan.execute`` (DESIGN.md §12): its counters
equal an independent recount, its spans cover every named host step and
appear nested in a profiler trace, blocks nest and stay per-thread, and a
repeated join compiles nothing."""
import glob
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from repro.core.geometry import size_buckets
from repro.core.join import _BUCKET_CHUNK, _pow2
from repro.datagen import make_dataset
from repro.kernels import count_routed, note_routed, pad_rows_pow2
from repro.runtime import trace
from repro.spatial import JoinPlan, JoinService
from repro.spatial.mbr_join import _prepare, candidate_rows

N_ORDER = 6
#: the fused chain as the chip benchmark pins it
FUSED = {"pipeline_mode": "fused", "mbr_backend": "jnp",
         "filter_backend": "pallas", "refine_backend": "pallas"}
SPANS = {"repro.join", "repro.mbr", "repro.filter", "repro.refine",
         "repro.mbr.frame", "repro.mbr.mask", "repro.filter.plan",
         "repro.filter.bucket", "repro.filter.args",
         "repro.filter.dispatch", "repro.refine.compact",
         "repro.refine.upload", "repro.refine.lanes", "repro.sync.gather",
         "repro.sync.escalate", "repro.join.assemble"}
#: counters that depend on what compiled before, not on the join
COMPILE_KEYS = ("compiles", "cache_loads")


@pytest.fixture(scope="module")
def plan():
    R = make_dataset("T1", seed=71, count=80)
    S = make_dataset("T2", seed=72, count=100)
    p = JoinPlan(R, S, filter="april", n_order=N_ORDER, **FUSED).build()
    p.execute("intersects")          # uploads the resident stores once
    return p


def _work(counters: dict) -> dict:
    return {k: v for k, v in counters.items() if k not in COMPILE_KEYS}


def _recount(plan) -> dict:
    """The warm join's counters, from its inputs alone."""
    mbrs_r, mbrs_s, k, extent = _prepare(plan.R.mbrs, plan.S.mbrs,
                                         plan.mbr_grid)
    ri, si, own_x, own_y, lo_r, lo_s = candidate_rows(mbrs_r, mbrs_s, k,
                                                      extent)
    N = len(ri)
    # the MBR mask's operands, uploaded in 64 bits
    h2d = sum(a.nbytes for a in pad_rows_pow2([mbrs_r, lo_r])[0]
              + pad_rows_pow2([mbrs_s, lo_s])[0]
              + pad_rows_pow2([ri, si, own_x, own_y,
                               np.ones(N, bool)])[0])
    lists = [plan.filter._lists(a, kind) for a, kind in (
        (plan.approx_r, "A"), (plan.approx_r, "F"),
        (plan.approx_s, "A"), (plan.approx_s, "F"))]
    counts = [L.counts(idx) for L, idx in zip(lists, (ri, ri, si, si))]
    live = (counts[0] > 0) & (counts[2] > 0)
    widths = np.maximum.reduce(counts)
    out = {"filter_buckets": 0, "filter_rows": 0, "filter_padded_rows": 0,
           "filter_gather_bytes": 0, "filter_compare_ops": 0}
    for sel in size_buckets(np.where(live, np.maximum(widths, 1), 0),
                            _BUCKET_CHUNK):
        Bp = _pow2(len(sel))
        wxa, wxf, wya, wyf = (_pow2(max(1, c[sel].max())) for c in counts)
        out["filter_buckets"] += 1
        out["filter_rows"] += len(sel)
        out["filter_padded_rows"] += Bp
        out["filter_gather_bytes"] += Bp * (wxa + wxf + wya + wyf) * 8
        # AA, AF and FA: two compares per x slot and y slot
        out["filter_compare_ops"] += Bp * 2 * (wxa * wya + wxa * wyf
                                               + wxf * wya)
        # per list: offsets and counts, int32 on the device (JAX narrows
        # the 64-bit host offsets); the lane scatter's int32 row indices
        h2d += 4 * Bp * (4 + 4) + 4 * len(sel)
    h2d += 2 * 4 * _pow2(N)          # the refine frame, int32 ri and si
    out["h2d_bytes"] = h2d
    # status (int8), hit, unc and valid (bool) over the frame
    out["d2h_bytes"] = 4 * N
    out["syncs"] = 1
    return out


def test_counters_equal_an_independent_recount(plan):
    _, st = plan.execute("intersects")
    assert _work(st.extra["counters"]) == _recount(plan)
    assert st.extra["counters"]["filter_padded_rows"] \
        >= st.extra["counters"]["filter_rows"]
    assert st.extra["counters"]["filter_compare_ops"] > 0


def test_spans_cover_every_host_step(plan):
    _, st = plan.execute("intersects")
    spans = st.extra["spans_s"]
    assert set(spans) == SPANS
    assert all(s >= 0 for s in spans.values())
    assert spans["repro.join"] >= spans["repro.mbr"] + spans["repro.filter"]
    assert spans["repro.filter.bucket"] >= (spans["repro.filter.args"]
                                            + spans["repro.filter.dispatch"])
    # the stage times are the stage spans' host seconds
    assert (st.t_mbr, st.t_filter, st.t_refine) == (
        spans["repro.mbr"], spans["repro.filter"], spans["repro.refine"])
    assert st.t_sync == (spans["repro.sync.gather"]
                         + spans["repro.sync.escalate"])
    assert set(st.extra["routed"]) == set(trace.ROUTED_KEYS)


def test_spans_nest_under_the_join_in_a_profiler_trace(plan, tmp_path):
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        plan.execute("intersects")
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
              for plane in ProfileData.from_file(files[0]).planes
              for line in plane.lines for ev in line.events
              if ev.name.startswith("repro.")]
    joins = [(s, e) for name, s, e in events if name == "repro.join"]
    assert len(joins) == 1
    lo, hi = joins[0]
    inner = {name for name, s, e in events
             if name != "repro.join" and lo <= s and e <= hi}
    assert inner == SPANS - {"repro.join"}


def test_blocks_nest_and_stay_per_thread(plan):
    R = make_dataset("T1", seed=81, count=60)
    S = make_dataset("T3", seed=82, count=20)
    other = JoinPlan(R, S, filter="april", n_order=N_ORDER, **FUSED).build()
    other.execute("intersects")
    alone = {p: _work(p.execute("intersects")[1].extra["counters"])
             for p in (plan, other)}
    assert alone[plan] != alone[other]
    got, errors = {}, []
    barrier = threading.Barrier(2)

    def worker(p):
        try:
            barrier.wait(timeout=60)
            for _ in range(3):
                got.setdefault(p, []).append(
                    _work(p.execute("intersects")[1].extra["counters"]))
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)

    with trace.trace_block() as outer:
        threads = [threading.Thread(target=worker, args=(p,))
                   for p in (plan, other)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
    assert not any(t.is_alive() for t in threads) and not errors
    for p in (plan, other):
        assert got[p] == [alone[p]] * 3
    # the threads' blocks do not report to this thread's block
    assert outer.counters == {} and outer.spans_s == {}

    # in one thread, an enclosing block receives the join's totals
    with trace.trace_block() as outer:
        trace.count("filter_buckets", 5)
        _, st = plan.execute("intersects")
    want = dict(_work(st.extra["counters"]))
    want["filter_buckets"] += 5
    assert _work(outer.counters) == want
    assert outer.spans_s["repro.join"] == st.extra["spans_s"]["repro.join"]


def test_a_repeated_join_compiles_nothing():
    R = make_dataset("T1", seed=91, count=37)
    S = make_dataset("T2", seed=92, count=41)
    p = JoinPlan(R, S, filter="april", n_order=N_ORDER, **FUSED).build()
    first = p.execute("intersects")[1].extra["counters"]
    second = p.execute("intersects")[1].extra["counters"]
    assert first.get("compiles", 0) > 0
    assert second.get("compiles", 0) == 0
    assert _work(first) != {} and _work(second)["syncs"] == 1


def test_outside_a_block_nothing_is_counted():
    trace.count("filter_buckets", 3)
    note_routed("filter_wide_rows_host", 2)
    with trace.span("repro.join"):
        pass
    with trace.trace_block() as block:
        pass
    assert block.counters == {} and block.spans_s == {}
    assert not any(block.routed.values())
    # the routed-row block is the trace module's, re-exported by kernels
    assert count_routed is trace.count_routed
    with count_routed() as routed:
        note_routed("filter_wide_rows_host", 2)
    assert routed["filter_wide_rows_host"] == 2


def test_the_service_reports_queue_wait_and_batch_compiles():
    R = make_dataset("T1", seed=101, count=50)
    svc = JoinService(method="april", n_order=N_ORDER,
                      pipeline_mode="fused")
    svc.register_dataset("d", R)
    tickets = [svc.submit("d", "selection", R.verts[i, :R.nverts[i]])
               for i in range(3)]
    svc.drain()
    extras = [t.wait(60.0).stats["extra"] for t in tickets]
    waits = [e["queue_wait_s"] for e in extras]
    assert all(w >= 0 for w in waits)
    assert waits[0] >= waits[2]      # submitted first, waited longest
    assert all(e["batch_compiles"] >= e["counters"].get("compiles", 0)
               + e["counters"].get("cache_loads", 0) for e in extras)
    assert all(0 <= e["batch_cache_loads"] <= e["batch_compiles"]
               for e in extras)
    assert all("repro.join" in e["spans_s"] for e in extras)


#: one service batch in a fresh process; prints its envelope's counts
_ONE_BATCH = """
import json
from repro.datagen import make_dataset
from repro.spatial import JoinService
R = make_dataset("T1", seed=111, count=40)
svc = JoinService(method="april", n_order=%d, pipeline_mode="fused")
svc.register_dataset("d", R)
ticket = svc.submit("d", "selection", R.verts[0, :R.nverts[0]])
svc.drain()
extra = ticket.wait(240.0).stats["extra"]
print(json.dumps({k: extra[k] for k in ("batch_compiles",
                                        "batch_cache_loads")}))
""" % N_ORDER


def test_a_warm_compile_cache_still_counts_the_batch_programs(tmp_path):
    """A process loads from the persistent cache only programs it has not
    run before, so a batch that builds programs shows them on a warm
    cache too: the same count, now as loads."""
    import repro.spatial
    src = os.path.dirname(os.path.dirname(os.path.dirname(
        repro.spatial.__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")
    cold, warm = (json.loads(subprocess.run(
        [sys.executable, "-c", _ONE_BATCH], env=env, check=True,
        capture_output=True, text=True, timeout=600).stdout.splitlines()[-1])
        for _ in range(2))
    assert cold["batch_compiles"] > 0
    assert warm["batch_cache_loads"] > 0
    assert warm["batch_compiles"] == cold["batch_compiles"]
